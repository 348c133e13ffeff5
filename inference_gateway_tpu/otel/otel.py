"""GenAI telemetry facade.

Capability parity with reference otel/otel.go:50-255: the same 7
GenAI-semconv instruments with spec'd bucket boundaries, the same record
methods (token usage, request duration, tool calls), Prometheus exposition
for the dedicated metrics listener, and OTLP push ingestion (JSON
encoding) with the reference's delta-only, attribute-allowlisted,
replay-capped semantics (otel/ingest.go).
"""

from __future__ import annotations

from typing import Any

from inference_gateway_tpu.otel.metrics import Histogram, Registry, replay_histogram
from inference_gateway_tpu.otel.tracing import Tracer
from inference_gateway_tpu.version import APPLICATION_NAME

TEAM_UNKNOWN = "unknown"

# Semconv-recommended boundaries (otel.go:80-83).
DURATION_BOUNDARIES = (0.01, 0.02, 0.04, 0.08, 0.16, 0.32, 0.64, 1.28, 2.56, 5.12, 10.24, 20.48, 40.96, 81.92)
TOKEN_BOUNDARIES = (1, 4, 16, 64, 256, 1024, 4096, 16384, 65536, 262144, 1048576, 4194304, 16777216, 67108864)
# Inter-token latency lives well under the request-duration scale: a
# 7B-class decode step is single-digit milliseconds on TPU, hundreds of
# ms through a saturated relay (ISSUE 3 token-level streaming metrics).
TPOT_BOUNDARIES = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5)
# Output throughput per stream, tokens/second.
TOKEN_RATE_BOUNDARIES = (1.0, 2.0, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0, 500.0, 1000.0, 2500.0)
# Asyncio scheduling lag (ISSUE 4 watchdog): healthy loops wake the
# heartbeat within a millisecond; a relay saturation stall is 10-100ms+.
EVENTLOOP_LAG_BOUNDARIES = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5)
# Engine step durations (ISSUE 4 timeline): kernel times are tens of µs
# on TPU, and a fused chunk of decode steps lands in the tens-of-ms band.
ENGINE_STEP_BOUNDARIES = (0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                          0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5)
# Host gap between chained decode chunks (ISSUE 14), in MILLISECONDS:
# a host-free steady state dispatches in tens of µs of Python; anything
# past 1 ms means host work (allocator loops, array assembly, uploads)
# crept back between chunks.
HOST_GAP_MS_BOUNDARIES = (0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
                          10.0, 25.0, 50.0)
# Schema→token-mask compile times (ISSUE 13): a cache hit is ~0; cold
# compiles run milliseconds for small schemas up to ~1s for deep
# generic-JSON grammars over large vocabularies.
SCHEMA_COMPILE_BOUNDARIES = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                             0.1, 0.25, 0.5, 1.0, 2.5, 5.0)
# XLA program compiles (ISSUE 19): tiny test models trace in tens of
# milliseconds; a 32-layer step program takes tens of seconds on a chip.
COMPILE_DURATION_BOUNDARIES = (0.01, 0.05, 0.1, 0.5, 1.0, 2.5, 5.0, 10.0,
                               30.0, 60.0, 120.0, 300.0)
# Gauges that refresh only while their source is busy carry a TTL, so an
# idle source's last reading ages out of the exposition instead of
# freezing there. Must exceed the sidecar OTLP push interval (15s
# default) with margin.
IDLE_GAUGE_TTL = 60.0

_BASE_LABELS = ("source", "team", "gen_ai_operation_name", "gen_ai_provider_name", "gen_ai_request_model")

# Data-point attributes accepted from untrusted pushers (ingest.go:22-32).
ALLOWED_PUSH_ATTRIBUTES = {
    "gen_ai.provider.name",
    "gen_ai.system",
    "gen_ai.request.model",
    "gen_ai.response.model",
    "gen_ai.operation.name",
    "gen_ai.token.type",
    "gen_ai.tool.name",
    "gen_ai.tool.type",
    "error.type",
}

MAX_REPLAY_OBSERVATIONS = 10000


class OpenTelemetry:
    def __init__(self, environment: str = "production", tracing_enable: bool = False,
                 tracing_otlp_endpoint: str = "", logger=None) -> None:
        self.logger = logger
        self.registry = Registry()
        r = self.registry
        self.token_usage = r.histogram(
            "gen_ai.client.token.usage", "Number of input and output tokens used per operation",
            _BASE_LABELS + ("gen_ai_token_type",), TOKEN_BOUNDARIES, unit="{token}",
        )
        self.server_request_duration = r.histogram(
            "gen_ai.server.request.duration", "Generative AI server request duration",
            _BASE_LABELS + ("error_type",), DURATION_BOUNDARIES, unit="s",
        )
        self.client_operation_duration = r.histogram(
            "gen_ai.client.operation.duration", "GenAI operation duration as observed by the client",
            _BASE_LABELS + ("error_type",), DURATION_BOUNDARIES, unit="s",
        )
        self.client_time_to_first_chunk = r.histogram(
            "gen_ai.client.operation.time_to_first_chunk", "Time to receive the first chunk of a streaming response",
            _BASE_LABELS + ("error_type",), DURATION_BOUNDARIES, unit="s",
        )
        self.server_time_to_first_token = r.histogram(
            "gen_ai.server.time_to_first_token", "Time to generate the first token of a response",
            _BASE_LABELS + ("error_type",), DURATION_BOUNDARIES, unit="s",
        )
        self.execute_tool_duration = r.histogram(
            "gen_ai.execute_tool.duration", "GenAI tool execution duration",
            _BASE_LABELS + ("gen_ai_tool_name", "gen_ai_tool_type"), DURATION_BOUNDARIES, unit="s",
        )
        self.tool_call_counter = r.counter(
            "inference_gateway.tool_calls", "Number of tool calls observed in model responses",
            _BASE_LABELS + ("gen_ai_tool_name", "gen_ai_tool_type"), unit="{call}",
        )
        # Resilience-layer instruments (ISSUE 1): breaker transitions,
        # retries, failover hops, and a current-state gauge.
        self.breaker_transition_counter = r.counter(
            "inference_gateway.resilience.breaker_transitions",
            "Circuit breaker state transitions per (provider, model)",
            ("gen_ai_provider_name", "gen_ai_request_model", "from_state", "to_state"),
            unit="{transition}",
        )
        self.breaker_state_gauge = r.gauge(
            "inference_gateway.resilience.breaker_state",
            "Current circuit state per (provider, model): 0=closed 1=half_open 2=open",
            ("gen_ai_provider_name", "gen_ai_request_model"),
        )
        self.retry_counter = r.counter(
            "inference_gateway.resilience.retries",
            "Upstream retries attempted by the resilience layer",
            ("gen_ai_provider_name", "gen_ai_request_model", "reason"), unit="{retry}",
        )
        self.failover_counter = r.counter(
            "inference_gateway.resilience.failovers",
            "Mid-request failovers to another pool deployment",
            ("alias", "from_provider", "to_provider"), unit="{failover}",
        )
        # Overload-protection instruments (ISSUE 2): admission ledger
        # gauges plus shed/drain counters, extending the PR 1 breaker
        # dashboards to self-inflicted saturation.
        self.overload_in_flight_gauge = r.gauge(
            "inference_gateway.overload.in_flight",
            "Admitted in-flight requests per endpoint class",
            ("endpoint_class",),
        )
        self.overload_queue_gauge = r.gauge(
            "inference_gateway.overload.queue_depth",
            "Admission wait-queue depth per endpoint class",
            ("endpoint_class",),
        )
        self.overload_shed_counter = r.counter(
            "inference_gateway.overload.shed",
            "Requests rejected by admission control (cap, shed, drain)",
            ("endpoint_class", "priority", "reason"), unit="{request}",
        )
        self.drain_counter = r.counter(
            "inference_gateway.overload.drain_events",
            "Graceful-drain lifecycle events (begun/completed/timed_out)",
            ("phase",), unit="{event}",
        )
        # Per-tenant isolation instruments (ISSUE 16): tenant-labelled
        # edge series. NEW instruments rather than a new label on the
        # overload series — adding a label to an existing exposition
        # breaks every pinned dashboard query against it.
        self.tenant_request_counter = r.counter(
            "inference_gateway.tenant.requests",
            "Admitted requests per tenant at the admission edge",
            ("tenant",), unit="{request}",
        )
        self.tenant_shed_counter = r.counter(
            "inference_gateway.tenant.shed",
            "Requests rejected by per-tenant quota or fairness shedding",
            ("tenant", "reason"), unit="{request}",
        )
        # ``source`` (PR 6 gauge convention) says whose view the value
        # is: "worker" in single-process mode, "cluster" when the value
        # is the shm-slab merge — quotas are cluster-wide, so the gauge
        # must be too (ISSUE 18 satellite fix).
        self.tenant_in_flight_gauge = r.gauge(
            "inference_gateway.tenant.in_flight",
            "In-flight requests per tenant (source=cluster: live-slab "
            "merge; source=worker: this process only)",
            ("tenant", "source"),
        )
        # Token-level streaming instruments (ISSUE 3): the per-token
        # latency visibility the ROADMAP north star is judged against —
        # TPOT from the SSE relay and the scheduler emit path, queue wait
        # from the sidecar's phase clock, per-stream output throughput.
        self.time_per_output_token = r.histogram(
            "gen_ai.server.time_per_output_token",
            "Inter-token latency (TPOT) observed on the streaming path",
            _BASE_LABELS, TPOT_BOUNDARIES, unit="s",
        )
        self.time_in_queue = r.histogram(
            "gen_ai.server.time_in_queue",
            "Time a request waited for a decode slot before prefill began",
            _BASE_LABELS, DURATION_BOUNDARIES, unit="s",
        )
        self.output_tokens_per_second = r.histogram(
            "gen_ai.server.output_tokens_per_second",
            "Completion tokens per second over a finished stream",
            _BASE_LABELS, TOKEN_RATE_BOUNDARIES, unit="{token}/s",
        )
        # Engine gauges (ISSUE 3): continuous-batching saturation signals
        # sampled from a co-hosted Engine/Scheduler.
        self.engine_slot_occupancy_gauge = r.gauge(
            "inference_gateway.engine.slot_occupancy",
            "Active decode slots / max_slots (0..1) per served model",
            ("gen_ai_request_model",),
        )
        self.engine_kv_utilization_gauge = r.gauge(
            "inference_gateway.engine.kv_page_utilization",
            "KV-cache pages in use / total pages (0..1) per served model",
            ("gen_ai_request_model",),
        )
        self.engine_queue_depth_gauge = r.gauge(
            "inference_gateway.engine.queue_depth",
            "Scheduler wait-queue depth per served model",
            ("gen_ai_request_model",),
        )
        self.engine_spec_acceptance_gauge = r.gauge(
            "inference_gateway.engine.spec_tokens_per_slot_round",
            "Speculative decoding acceptance: emitted tokens per slot round",
            ("gen_ai_request_model",),
        )
        # Performance-introspection instruments (ISSUE 4): event-loop
        # scheduling health from the watchdog heartbeat, per-step engine
        # timing from the decode timeline, and slow-request breaches.
        self.eventloop_lag = r.histogram(
            "eventloop.lag",
            "Asyncio scheduling lag observed by the watchdog heartbeat",
            ("source",), EVENTLOOP_LAG_BOUNDARIES, unit="s",
        )
        self.eventloop_stall_counter = r.counter(
            "eventloop.stalls",
            "Event-loop stalls: heartbeat lag above the watchdog threshold",
            ("source",), unit="{stall}",
        )
        self.engine_step_duration = r.histogram(
            "engine.step_duration",
            "Engine step wall time by kind (prefill/decode/spec/spec_ngram)",
            ("gen_ai_request_model", "kind"), ENGINE_STEP_BOUNDARIES, unit="s",
        )
        self.engine_host_gap = r.histogram(
            "engine.host_gap_ms",
            "Host wall time between fetching chunk N and dispatching chunk "
            "N+1 — the direct measure of the host-free decode steady state",
            ("gen_ai_request_model", "kind"), HOST_GAP_MS_BOUNDARIES, unit="ms",
        )
        self.slow_request_counter = r.counter(
            "inference_gateway.slow_requests",
            "Requests breaching the configured TTFT/TPOT/total latency thresholds",
            ("source", "breach"), unit="{request}",
        )
        # Wasted-work attribution (ISSUE 6): what the scheduler counted,
        # handed over by the sidecar.
        self.wasted_tokens_counter = r.counter(
            "engine.wasted_tokens",
            "Tokens computed but never delivered, by reason "
            "(spec_rejected/chunk_overrun/overshoot/disconnected/shed_after_prefill)",
            ("gen_ai_request_model", "reason"), unit="{token}",
        )
        # Serving-path fault tolerance (ISSUE 7): KV-pressure preemption,
        # engine hang watchdog restarts, pre-first-byte stream recovery,
        # and the degraded-state gauge the restart window flips so
        # failover pools (and dashboards) see the sidecar route-around.
        self.engine_preemption_counter = r.counter(
            "engine.preemptions",
            "Requests descheduled under KV pressure (slot+pages released, "
            "re-enqueued for recompute-style resume), by trigger",
            ("gen_ai_request_model", "reason"), unit="{preemption}",
        )
        self.engine_restart_counter = r.counter(
            "engine.restarts",
            "Supervised in-place engine rebuilds after a wedged device step",
            ("gen_ai_request_model", "reason"), unit="{restart}",
        )
        self.streams_recovered_counter = r.counter(
            "inference_gateway.streams_recovered",
            "Streamed requests transparently failed over after the upstream "
            "died: phase=pre_first_byte re-issues the request, "
            "phase=post_first_byte continues it with the relayed prefix "
            "spliced (ISSUE 9)",
            ("alias", "from_provider", "to_provider", "phase"), unit="{stream}",
        )
        self.engine_degraded_gauge = r.gauge(
            "engine.degraded",
            "1 while the serving engine is restarting (health reports 503 "
            "degraded so pools route around the window), else 0",
            ("gen_ai_request_model",),
        )
        # Paged-attention dispatch verdict (ISSUE 12 satellite): which
        # path this engine's layouts take. 1 on the active path, 0 on
        # the others — a silently-degraded gather deployment (the
        # ~10.6×-slower fallback) alerts on engine.attention_path
        # {path="gather"} == 1 instead of hiding in XLA dumps.
        self.engine_attention_path_gauge = r.gauge(
            "engine.attention_path",
            "Active paged-attention dispatch path (1 = the engine's layouts "
            "take this path): kernel / kernel_sharded / kernel_replicated / "
            "gather (the ~10.6x-slower GSPMD fallback) / dense (no paging)",
            ("gen_ai_request_model", "path"),
        )
        # Active pool health probing (ISSUE 9): per-deployment probe
        # verdict plus ejection/readmission lifecycle counters. The
        # gauge is set to 1 for every probed target at prober start —
        # an absent series must never read as healthy.
        self.pool_healthy_gauge = r.gauge(
            "inference_gateway.pool_healthy",
            "Active-probe verdict per pool deployment: 1 healthy, "
            "0 probe-ejected (zero establishment attempts until readmission)",
            ("gen_ai_provider_name", "gen_ai_request_model"),
        )
        self.probe_ejection_counter = r.counter(
            "inference_gateway.probe_ejections",
            "Pool deployments ejected after K consecutive health-probe failures",
            ("gen_ai_provider_name", "gen_ai_request_model"), unit="{ejection}",
        )
        self.probe_readmission_counter = r.counter(
            "inference_gateway.probe_readmissions",
            "Probe-ejected pool deployments readmitted on probe recovery",
            ("gen_ai_provider_name", "gen_ai_request_model"), unit="{readmission}",
        )
        # Fleet routing instruments (ISSUE 11): prefix-affinity outcomes,
        # planned live migrations, and the per-deployment load reported
        # through the /health body the prober doubles as collector for.
        self.affinity_hit_counter = r.counter(
            "inference_gateway.routing.affinity_hits",
            "Pool requests routed to their ring-affine deployment "
            "(prefix-cache locality preserved)",
            ("alias",), unit="{request}",
        )
        self.affinity_spill_counter = r.counter(
            "inference_gateway.routing.affinity_spills",
            "Pool requests spilled off their affine deployment, by reason "
            "(saturated = bounded-load spill, unhealthy = breaker/probe/drain)",
            ("alias", "reason"), unit="{request}",
        )
        self.streams_migrated_counter = r.counter(
            "inference_gateway.streams_migrated",
            "Live streams PROACTIVELY moved to another replica via the "
            "continuation splice, by reason (drain = planned drain, "
            "restart = supervised engine restart) — a subset of "
            "streams_recovered{phase=post_first_byte}",
            ("alias", "from_provider", "to_provider", "reason"), unit="{stream}",
        )
        self.deployment_load_gauge = r.gauge(
            "inference_gateway.routing.deployment_load",
            "Last load report per pool deployment, by signal "
            "(queue_depth / kv_page_utilization / active_slots / max_slots) "
            "— parsed from the /health body by the health prober",
            ("gen_ai_provider_name", "gen_ai_request_model", "signal"),
            ttl=IDLE_GAUGE_TTL,
        )
        # Structured outputs (ISSUE 13): constrained-request outcomes,
        # schema→token-mask compile cost, and mask-cache effectiveness
        # (shared schemas should hit like prompt prefixes hit the
        # PrefixCache — a cold-compile-per-request deployment is a
        # misconfiguration this counter makes visible).
        self.constrained_requests_counter = r.counter(
            "engine.constrained_requests",
            "Grammar-constrained (response_format) requests served, by "
            "finish outcome (stop = grammar/EOS completed the document; "
            "length/error/disconnected = truncated or failed)",
            ("gen_ai_request_model", "outcome"), unit="{request}",
        )
        self.schema_compile_duration = r.histogram(
            "engine.schema_compile.duration",
            "JSON Schema -> token-mask automaton compile time (cold "
            "compiles only; cache hits record on the mask-cache counter)",
            ("gen_ai_request_model",), SCHEMA_COMPILE_BOUNDARIES, unit="s",
        )
        self.mask_cache_counter = r.counter(
            "engine.mask_cache.lookups",
            "Compiled-grammar cache lookups by result (hit/miss) — shared "
            "schemas repeat across requests like prompt prefixes",
            ("gen_ai_request_model", "result"), unit="{lookup}",
        )
        # Fleet observability plane (ISSUE 18): SLO burn rates per
        # tenant and per pool (cluster-merged at scrape time — the same
        # series from any worker), and journey lifecycle event counts.
        # Cardinality is bounded by construction: slo/window/event label
        # values are closed vocabularies, tenant keys fold into hashed
        # overflow buckets past SLO_MAX_TENANT_SERIES, and NO instrument
        # ever carries a trace id (journeys are /debug/journey's job —
        # the metric-lint cardinality rule pins this).
        self.slo_burn_rate_gauge = r.gauge(
            "inference_gateway.slo.burn_rate",
            "Error-budget burn rate per tenant SLO and window (1.0 = "
            "consuming the budget exactly as fast as the window allows)",
            ("slo", "window", "tenant"), ttl=IDLE_GAUGE_TTL,
        )
        self.slo_budget_gauge = r.gauge(
            "inference_gateway.slo.error_budget_remaining",
            "Error budget remaining per tenant SLO and window "
            "(1 - burn_rate; negative = overspent)",
            ("slo", "window", "tenant"), ttl=IDLE_GAUGE_TTL,
        )
        self.slo_pool_burn_rate_gauge = r.gauge(
            "inference_gateway.slo.pool_burn_rate",
            "Error-budget burn rate per pool SLO and window",
            ("slo", "window", "pool"), ttl=IDLE_GAUGE_TTL,
        )
        self.slo_pool_budget_gauge = r.gauge(
            "inference_gateway.slo.pool_error_budget_remaining",
            "Error budget remaining per pool SLO and window",
            ("slo", "window", "pool"), ttl=IDLE_GAUGE_TTL,
        )
        self.journey_event_counter = r.counter(
            "inference_gateway.journey.events",
            "Stream-journey lifecycle events recorded (admitted/routed/"
            "first_byte/recovered/migrated/spliced/finished/shed)",
            ("event",), unit="{event}",
        )
        # Device observatory (ISSUE 19): XLA compile ledger, steady-state
        # recompile detection, live HBM accounting, and the always-on
        # host<->device transfer audit. Label vocabularies are closed
        # (program = the engine's jitted entry points; direction/path =
        # the submit/fetch seams), so cardinality is bounded by code.
        self.engine_compile_duration = r.histogram(
            "engine.compile_duration",
            "XLA compile wall time per jitted engine program (warmup AND "
            "steady-state; recompiles also count on engine.recompiles)",
            ("gen_ai_request_model", "program"), COMPILE_DURATION_BOUNDARIES,
            unit="s",
        )
        self.engine_recompile_counter = r.counter(
            "engine.recompiles",
            "Steady-state XLA recompiles (any compile after engine warmup "
            "completed) — the silent TPU throughput killer; the triggering "
            "shape-signature diff rides the wide event",
            ("gen_ai_request_model", "program"), unit="{compile}",
        )
        self.engine_transfer_counter = r.counter(
            "engine.transfers",
            "Host<->device transfers staged at the engine submit/fetch "
            "seams, by direction (h2d/d2h) and path (prefill/decode/fresh/"
            "chain/chunk/mixed/spec). The PR 14 invariant live: "
            "{direction=h2d,path=chain} must read 0 on any worker",
            ("gen_ai_request_model", "direction", "path"), unit="{transfer}",
        )
        self.engine_transfer_bytes_counter = r.counter(
            "engine.transfer_bytes",
            "Best-effort bytes of the host arrays staged per transfer "
            "(small scalars and RNG keys are not itemized)",
            ("gen_ai_request_model", "direction", "path"), unit="By",
        )
        self.engine_hbm_live_gauge = r.gauge(
            "engine.hbm.live_bytes",
            "Device bytes in use from device.memory_stats() — only set "
            "when the backend measures it (absent off-TPU, never fabricated)",
            ("gen_ai_request_model",), ttl=IDLE_GAUGE_TTL,
        )
        self.engine_hbm_peak_gauge = r.gauge(
            "engine.hbm.peak_bytes",
            "Peak device bytes in use from device.memory_stats() — only "
            "set when the backend measures it",
            ("gen_ai_request_model",), ttl=IDLE_GAUGE_TTL,
        )
        self.engine_hbm_plan_gauge = r.gauge(
            "engine.hbm.plan_bytes",
            "Analytic device-byte plan (weights at serving dtype + KV pool "
            "reservation) computed from the live engine's config",
            ("gen_ai_request_model",),
        )
        self.tracer = Tracer(
            APPLICATION_NAME, otlp_endpoint=tracing_otlp_endpoint,
            enabled=tracing_enable, logger=logger,
        )

    # -- record methods (otel.go:205-247) --------------------------------
    @staticmethod
    def _base(source: str, team: str, provider: str, model: str) -> dict[str, str]:
        return {
            "source": source,
            "team": team or TEAM_UNKNOWN,
            "gen_ai_operation_name": "chat",
            "gen_ai_provider_name": provider,
            "gen_ai_request_model": model,
        }

    def record_token_usage(self, source: str, team: str, provider: str, model: str,
                           input_tokens: int, output_tokens: int) -> None:
        base = self._base(source, team, provider, model)
        self.token_usage.record(input_tokens, {**base, "gen_ai_token_type": "input"})
        self.token_usage.record(output_tokens, {**base, "gen_ai_token_type": "output"})

    def record_request_duration(self, source: str, team: str, provider: str, model: str,
                                error_type: str, seconds: float) -> None:
        labels = self._base(source, team, provider, model)
        if error_type:
            labels["error_type"] = error_type
        self.server_request_duration.record(seconds, labels)

    def record_tool_call(self, source: str, team: str, provider: str, model: str,
                         tool_type: str, tool_name: str) -> None:
        labels = self._base(source, team, provider, model)
        labels.pop("gen_ai_operation_name")
        labels.update({"gen_ai_tool_name": tool_name, "gen_ai_tool_type": tool_type})
        self.tool_call_counter.add(1, labels)

    # -- resilience (ISSUE 1) --------------------------------------------
    def record_breaker_transition(self, provider: str, model: str, old: str, new: str) -> None:
        self.breaker_transition_counter.add(1, {
            "gen_ai_provider_name": provider, "gen_ai_request_model": model,
            "from_state": old, "to_state": new,
        })

    def set_breaker_state(self, provider: str, model: str, state_code: int) -> None:
        self.breaker_state_gauge.set(state_code, {
            "gen_ai_provider_name": provider, "gen_ai_request_model": model,
        })

    def record_retry(self, provider: str, model: str, reason: str) -> None:
        self.retry_counter.add(1, {
            "gen_ai_provider_name": provider, "gen_ai_request_model": model,
            "reason": reason,
        })

    def record_failover(self, alias: str, from_provider: str, to_provider: str) -> None:
        self.failover_counter.add(1, {
            "alias": alias, "from_provider": from_provider, "to_provider": to_provider,
        })

    # -- overload protection (ISSUE 2) -----------------------------------
    def set_overload_in_flight(self, endpoint_class: str, value: int) -> None:
        self.overload_in_flight_gauge.set(value, {"endpoint_class": endpoint_class})

    def set_overload_queue_depth(self, endpoint_class: str, value: int) -> None:
        self.overload_queue_gauge.set(value, {"endpoint_class": endpoint_class})

    def record_overload_shed(self, endpoint_class: str, priority: str, reason: str) -> None:
        self.overload_shed_counter.add(1, {
            "endpoint_class": endpoint_class, "priority": priority, "reason": reason,
        })

    def record_drain_event(self, phase: str) -> None:
        self.drain_counter.add(1, {"phase": phase})

    # -- per-tenant isolation (ISSUE 16) ---------------------------------
    def record_tenant_request(self, tenant: str) -> None:
        self.tenant_request_counter.add(1, {"tenant": tenant})

    def record_tenant_shed(self, tenant: str, reason: str) -> None:
        self.tenant_shed_counter.add(1, {"tenant": tenant, "reason": reason})

    def set_tenant_in_flight(self, tenant: str, value: int,
                             source: str = "worker") -> None:
        self.tenant_in_flight_gauge.set(value, {"tenant": tenant,
                                                "source": source})

    def remove_tenant_gauge(self, tenant: str, source: str = "worker") -> None:
        """A tenant back at zero in-flight leaves the exposition: tenant
        ids are unbounded (hashed API keys), so idle series must be
        dropped or the gauge cardinality only ever grows."""
        self.tenant_in_flight_gauge.remove({"tenant": tenant,
                                            "source": source})

    # -- fleet observability (ISSUE 18) ----------------------------------
    def set_slo_burn_rate(self, slo: str, window: str, tenant: str,
                          burn: float, remaining: float) -> None:
        labels = {"slo": slo, "window": window, "tenant": tenant}
        self.slo_burn_rate_gauge.set(burn, labels)
        self.slo_budget_gauge.set(remaining, labels)

    def set_pool_slo_burn_rate(self, slo: str, window: str, pool: str,
                               burn: float, remaining: float) -> None:
        labels = {"slo": slo, "window": window, "pool": pool}
        self.slo_pool_burn_rate_gauge.set(burn, labels)
        self.slo_pool_budget_gauge.set(remaining, labels)

    def record_journey_event(self, event: str) -> None:
        self.journey_event_counter.add(1, {"event": event})

    # -- token-level streaming metrics (ISSUE 3) -------------------------
    def record_time_to_first_chunk(self, source: str, team: str, provider: str,
                                   model: str, seconds: float) -> None:
        self.client_time_to_first_chunk.record(
            seconds, self._base(source, team, provider, model))

    def record_server_ttft(self, source: str, team: str, provider: str,
                           model: str, seconds: float) -> None:
        self.server_time_to_first_token.record(
            seconds, self._base(source, team, provider, model))

    def record_tpot(self, source: str, team: str, provider: str, model: str,
                    seconds: float) -> None:
        self.time_per_output_token.record(
            seconds, self._base(source, team, provider, model))

    def record_queue_wait(self, source: str, team: str, provider: str, model: str,
                          seconds: float) -> None:
        self.time_in_queue.record(seconds, self._base(source, team, provider, model))

    def record_output_token_rate(self, source: str, team: str, provider: str,
                                 model: str, tokens_per_second: float) -> None:
        self.output_tokens_per_second.record(
            tokens_per_second, self._base(source, team, provider, model))

    # -- engine gauges (ISSUE 3) -----------------------------------------
    def set_engine_gauges(self, model: str, *, slot_occupancy: float | None = None,
                          kv_utilization: float | None = None,
                          queue_depth: int | None = None,
                          spec_tokens_per_slot_round: float | None = None) -> None:
        labels = {"gen_ai_request_model": model}
        if slot_occupancy is not None:
            self.engine_slot_occupancy_gauge.set(slot_occupancy, labels)
        if kv_utilization is not None:
            self.engine_kv_utilization_gauge.set(kv_utilization, labels)
        if queue_depth is not None:
            self.engine_queue_depth_gauge.set(queue_depth, labels)
        if spec_tokens_per_slot_round is not None:
            self.engine_spec_acceptance_gauge.set(spec_tokens_per_slot_round, labels)

    def remove_engine_gauges(self, model: str) -> None:
        """Engine teardown: drop the model's saturation series so a gone
        engine stops being exposed as current state (ISSUE 4 satellite)."""
        labels = {"gen_ai_request_model": model}
        for gauge in (self.engine_slot_occupancy_gauge, self.engine_kv_utilization_gauge,
                      self.engine_queue_depth_gauge, self.engine_spec_acceptance_gauge,
                      self.engine_degraded_gauge):
            gauge.remove(labels)
        for p in self.ATTENTION_PATHS:
            self.engine_attention_path_gauge.remove(
                {"gen_ai_request_model": model, "path": p})

    def remove_overload_gauges(self, endpoint_class: str) -> None:
        """Drain completion: the admission ledger's per-class series stop
        describing anything once the gateway is out of rotation."""
        labels = {"endpoint_class": endpoint_class}
        self.overload_in_flight_gauge.remove(labels)
        self.overload_queue_gauge.remove(labels)

    # -- performance introspection (ISSUE 4) -----------------------------
    def record_eventloop_lag(self, source: str, seconds: float) -> None:
        self.eventloop_lag.record(seconds, {"source": source})

    def record_eventloop_stall(self, source: str) -> None:
        self.eventloop_stall_counter.add(1, {"source": source})

    def record_host_gap(self, model: str, kind: str, gap_ms: float) -> None:
        self.engine_host_gap.record(
            gap_ms, {"gen_ai_request_model": model, "kind": kind})

    def record_engine_step(self, model: str, kind: str, seconds: float) -> None:
        self.engine_step_duration.record(
            seconds, {"gen_ai_request_model": model, "kind": kind})

    def record_slow_request(self, source: str, breach: str) -> None:
        self.slow_request_counter.add(1, {"source": source, "breach": breach})

    # -- wasted-work attribution (ISSUE 6) -------------------------------
    def record_wasted_tokens(self, model: str, reason: str, tokens: int = 1) -> None:
        self.wasted_tokens_counter.add(
            tokens, {"gen_ai_request_model": model, "reason": reason})

    # -- serving-path fault tolerance (ISSUE 7) --------------------------
    def record_preemption(self, model: str, reason: str) -> None:
        self.engine_preemption_counter.add(1, {
            "gen_ai_request_model": model, "reason": reason})

    def record_engine_restart(self, model: str, reason: str) -> None:
        self.engine_restart_counter.add(1, {
            "gen_ai_request_model": model, "reason": reason})

    def record_stream_recovered(self, alias: str, from_provider: str,
                                to_provider: str,
                                phase: str = "pre_first_byte") -> None:
        self.streams_recovered_counter.add(1, {
            "alias": alias, "from_provider": from_provider,
            "to_provider": to_provider, "phase": phase})

    def set_engine_degraded(self, model: str, value: int) -> None:
        self.engine_degraded_gauge.set(value, {"gen_ai_request_model": model})

    # -- paged-attention dispatch verdict (ISSUE 12) ---------------------
    ATTENTION_PATHS = ("kernel", "kernel_sharded", "kernel_replicated",
                      "gather", "dense")

    def set_attention_path(self, model: str, path: str) -> None:
        """1 on the active dispatch path, explicit 0 on every other —
        an absent series must never read as 'not on gather'."""
        for p in self.ATTENTION_PATHS:
            self.engine_attention_path_gauge.set(
                1 if p == path else 0, {"gen_ai_request_model": model, "path": p})

    # -- active pool health probing (ISSUE 9) ----------------------------
    def set_pool_healthy(self, provider: str, model: str, value: int) -> None:
        self.pool_healthy_gauge.set(value, {
            "gen_ai_provider_name": provider, "gen_ai_request_model": model})

    def record_probe_ejection(self, provider: str, model: str) -> None:
        self.probe_ejection_counter.add(1, {
            "gen_ai_provider_name": provider, "gen_ai_request_model": model})

    def record_probe_readmission(self, provider: str, model: str) -> None:
        self.probe_readmission_counter.add(1, {
            "gen_ai_provider_name": provider, "gen_ai_request_model": model})

    # -- fleet routing (ISSUE 11) ----------------------------------------
    def record_affinity_hit(self, alias: str) -> None:
        self.affinity_hit_counter.add(1, {"alias": alias})

    def record_affinity_spill(self, alias: str, reason: str) -> None:
        self.affinity_spill_counter.add(1, {"alias": alias, "reason": reason})

    def record_stream_migrated(self, alias: str, from_provider: str,
                               to_provider: str, reason: str) -> None:
        self.streams_migrated_counter.add(1, {
            "alias": alias, "from_provider": from_provider,
            "to_provider": to_provider, "reason": reason})

    def set_deployment_load(self, provider: str, model: str, signal: str,
                            value: float) -> None:
        self.deployment_load_gauge.set(value, {
            "gen_ai_provider_name": provider, "gen_ai_request_model": model,
            "signal": signal})

    # -- structured outputs (ISSUE 13) -----------------------------------
    def record_constrained_request(self, model: str, outcome: str) -> None:
        self.constrained_requests_counter.add(1, {
            "gen_ai_request_model": model, "outcome": outcome})

    def record_schema_compile(self, model: str, seconds: float,
                              cache_hit: bool) -> None:
        """One response_format compile: cache hits count on the lookup
        counter only (a hit's ~0s would drown the compile histogram)."""
        self.mask_cache_counter.add(1, {
            "gen_ai_request_model": model,
            "result": "hit" if cache_hit else "miss"})
        if not cache_hit:
            self.schema_compile_duration.record(
                seconds, {"gen_ai_request_model": model})

    # -- device observatory (ISSUE 19) -----------------------------------
    def record_compile(self, model: str, program: str, seconds: float,
                       recompile: bool = False) -> None:
        """One XLA compile from the engine's compile ledger; steady-state
        recompiles additionally count on engine.recompiles (the alert
        series — warmup compiles are expected, these are not)."""
        self.engine_compile_duration.record(
            seconds, {"gen_ai_request_model": model, "program": program})
        if recompile:
            self.engine_recompile_counter.add(
                1, {"gen_ai_request_model": model, "program": program})

    def record_transfer(self, model: str, direction: str, path: str,
                        count: int, nbytes: int) -> None:
        """Transfer-audit seam; count=0 pre-seeds a series at an explicit
        zero (the h2d/chain invariant must be scrapeable, not absent)."""
        labels = {"gen_ai_request_model": model, "direction": direction,
                  "path": path}
        self.engine_transfer_counter.add(count, labels)
        self.engine_transfer_bytes_counter.add(nbytes, labels)

    def set_hbm_bytes(self, model: str, *, plan: int | None = None,
                      live: int | None = None, peak: int | None = None) -> None:
        """HBM gauges: live/peak only when the backend measured them —
        an off-TPU host sets the plan gauge alone, and the absent
        live/peak series are the honest 'not measured' (never 0, never
        the plan echoed back)."""
        labels = {"gen_ai_request_model": model}
        if plan is not None:
            self.engine_hbm_plan_gauge.set(plan, labels)
        if live is not None:
            self.engine_hbm_live_gauge.set(live, labels)
        if peak is not None:
            self.engine_hbm_peak_gauge.set(peak, labels)

    def remove_hbm_gauges(self, model: str) -> None:
        labels = {"gen_ai_request_model": model}
        for gauge in (self.engine_hbm_live_gauge, self.engine_hbm_peak_gauge,
                      self.engine_hbm_plan_gauge):
            gauge.remove(labels)

    def expose_prometheus(self) -> str:
        return self.registry.expose()

    # -- OTLP push ingest (ingest.go:37-218) -----------------------------
    def ingest_metrics(self, payload: dict[str, Any], source: str) -> dict[str, int | str]:
        """Map a pushed OTLP-JSON payload onto internal instruments.

        Delta-only for sums/histograms; attributes filtered to the
        allowlist; histograms replayed at bucket midpoints capped at
        10k observations; the pusher's service.name becomes the source
        label unless it impersonates the gateway (ingest.go:190-218).
        """
        accepted = 0
        rejected = 0
        reasons: list[str] = []

        def reject(points: int, reason: str) -> None:
            nonlocal rejected
            rejected += points
            if reason not in reasons:
                reasons.append(reason)

        name_to_hist: dict[str, Histogram] = {
            "gen_ai.client.token.usage": self.token_usage,
            "gen_ai.client.operation.duration": self.client_operation_duration,
            "gen_ai.server.request.duration": self.server_request_duration,
            "gen_ai.client.operation.time_to_first_chunk": self.client_time_to_first_chunk,
            "gen_ai.server.time_to_first_token": self.server_time_to_first_token,
            "gen_ai.execute_tool.duration": self.execute_tool_duration,
            # Sidecar-pushed token-level streaming metrics (ISSUE 3).
            "gen_ai.server.time_per_output_token": self.time_per_output_token,
            "gen_ai.server.time_in_queue": self.time_in_queue,
            "gen_ai.server.output_tokens_per_second": self.output_tokens_per_second,
        }

        # Gauges pushed by a standalone sidecar: last-value semantics, so
        # ingest is a plain set.
        name_to_gauge = {
            # Device observatory (ISSUE 19): a standalone sidecar pushes
            # its HBM accounting so the gateway-side exposition carries
            # every worker's device story. Note the live/peak series only
            # arrive from hosts that measured them.
            "engine.hbm.live_bytes": self.engine_hbm_live_gauge,
            "engine.hbm.peak_bytes": self.engine_hbm_peak_gauge,
            "engine.hbm.plan_bytes": self.engine_hbm_plan_gauge,
        }

        for rm in payload.get("resourceMetrics") or []:
            svc = _resource_service_name(rm) or source
            if svc == APPLICATION_NAME:
                svc = f"push:{source or 'unknown'}"  # anti-impersonation
            for sm in rm.get("scopeMetrics") or []:
                for m in sm.get("metrics") or []:
                    name = m.get("name", "")
                    gauge = name_to_gauge.get(name)
                    if gauge is not None:
                        accepted += self._ingest_gauge(m, gauge, svc)
                        continue
                    if name == "inference_gateway.tool_calls":
                        accepted_pts, msg = self._ingest_sum(m, svc)
                        accepted += accepted_pts
                        if msg:
                            reject(self._point_count(m), msg)
                        continue
                    hist = name_to_hist.get(name)
                    if hist is None:
                        reject(self._point_count(m), f"unsupported metric {name!r}")
                        continue
                    accepted_pts, msg = self._ingest_histogram(m, hist, svc)
                    accepted += accepted_pts
                    if msg:
                        reject(self._point_count(m), msg)

        result: dict[str, int | str] = {"accepted": accepted, "rejected": rejected}
        if reasons:
            result["error_message"] = "; ".join(reasons)
        return result

    @staticmethod
    def _point_count(metric: dict[str, Any]) -> int:
        body = metric.get("histogram") or metric.get("sum") or metric.get("gauge") or {}
        return len(body.get("dataPoints") or [])

    def _ingest_gauge(self, metric: dict[str, Any], gauge, svc: str) -> int:
        accepted = 0
        for dp in (metric.get("gauge") or {}).get("dataPoints") or []:
            val = dp.get("asDouble")
            if val is None:
                val = dp.get("asInt")
            if val is None:
                continue
            labels = self._labels_from(dp.get("attributes"), svc)
            gauge.set(float(val), labels)
            accepted += 1
        return accepted

    @staticmethod
    def _labels_from(attrs: list[dict[str, Any]], svc: str) -> dict[str, str]:
        labels = {"source": svc, "team": TEAM_UNKNOWN}
        for a in attrs or []:
            key = a.get("key", "")
            if key not in ALLOWED_PUSH_ATTRIBUTES:
                continue
            if key == "gen_ai.system":
                key = "gen_ai.provider.name"
            val = a.get("value") or {}
            sval = val.get("stringValue") or str(val.get("intValue") or val.get("doubleValue") or "")
            labels[key.replace(".", "_")] = sval
        return labels

    def _ingest_sum(self, metric: dict[str, Any], svc: str) -> tuple[int, str]:
        sum_body = metric.get("sum") or {}
        if sum_body.get("aggregationTemporality") not in (1, "AGGREGATION_TEMPORALITY_DELTA"):
            return 0, "cumulative temporality not supported; push deltas"
        accepted = 0
        for dp in sum_body.get("dataPoints") or []:
            val = int(dp.get("asInt") or dp.get("asDouble") or 0)
            labels = self._labels_from(dp.get("attributes"), svc)
            if val > 0:
                self.tool_call_counter.add(val, labels)
                accepted += 1
        return accepted, ""

    def _ingest_histogram(self, metric: dict[str, Any], hist: Histogram, svc: str) -> tuple[int, str]:
        body = metric.get("histogram") or {}
        if body.get("aggregationTemporality") not in (1, "AGGREGATION_TEMPORALITY_DELTA"):
            return 0, "cumulative temporality not supported; push deltas"
        accepted = 0
        for dp in body.get("dataPoints") or []:
            labels = self._labels_from(dp.get("attributes"), svc)
            counts = [int(c) for c in dp.get("bucketCounts") or []]
            bounds = [float(b) for b in dp.get("explicitBounds") or []]
            if counts and len(counts) == len(bounds) + 1:
                replay_histogram(hist, counts, bounds, labels, cap=MAX_REPLAY_OBSERVATIONS)
                accepted += 1
            elif dp.get("sum") is not None and int(dp.get("count") or 0) > 0:
                count = min(int(dp["count"]), MAX_REPLAY_OBSERVATIONS)
                avg = float(dp["sum"]) / int(dp["count"])
                for _ in range(count):
                    hist.record(avg, labels)
                accepted += 1
        return accepted, ""


def _resource_service_name(rm: dict[str, Any]) -> str:
    for a in (rm.get("resource") or {}).get("attributes") or []:
        if a.get("key") == "service.name":
            return (a.get("value") or {}).get("stringValue", "")
    return ""


class NoopTelemetry(OpenTelemetry):
    """Telemetry disabled: records go nowhere cheap."""

    def record_token_usage(self, *a, **k) -> None:
        pass

    def record_request_duration(self, *a, **k) -> None:
        pass

    def record_tool_call(self, *a, **k) -> None:
        pass

    def record_breaker_transition(self, *a, **k) -> None:
        pass

    def set_breaker_state(self, *a, **k) -> None:
        pass

    def record_retry(self, *a, **k) -> None:
        pass

    def record_failover(self, *a, **k) -> None:
        pass

    def set_overload_in_flight(self, *a, **k) -> None:
        pass

    def set_overload_queue_depth(self, *a, **k) -> None:
        pass

    def record_overload_shed(self, *a, **k) -> None:
        pass

    def record_drain_event(self, *a, **k) -> None:
        pass

    def record_tenant_request(self, *a, **k) -> None:
        pass

    def record_tenant_shed(self, *a, **k) -> None:
        pass

    def set_tenant_in_flight(self, *a, **k) -> None:
        pass

    def remove_tenant_gauge(self, *a, **k) -> None:
        pass

    def set_slo_burn_rate(self, *a, **k) -> None:
        pass

    def set_pool_slo_burn_rate(self, *a, **k) -> None:
        pass

    def record_journey_event(self, *a, **k) -> None:
        pass

    def record_time_to_first_chunk(self, *a, **k) -> None:
        pass

    def record_server_ttft(self, *a, **k) -> None:
        pass

    def record_tpot(self, *a, **k) -> None:
        pass

    def record_queue_wait(self, *a, **k) -> None:
        pass

    def record_output_token_rate(self, *a, **k) -> None:
        pass

    def set_engine_gauges(self, *a, **k) -> None:
        pass

    def remove_engine_gauges(self, *a, **k) -> None:
        pass

    def remove_overload_gauges(self, *a, **k) -> None:
        pass

    def record_eventloop_lag(self, *a, **k) -> None:
        pass

    def record_eventloop_stall(self, *a, **k) -> None:
        pass

    def record_engine_step(self, *a, **k) -> None:
        pass

    def record_host_gap(self, *a, **k) -> None:
        pass

    def record_slow_request(self, *a, **k) -> None:
        pass

    def record_wasted_tokens(self, *a, **k) -> None:
        pass

    def record_preemption(self, *a, **k) -> None:
        pass

    def record_engine_restart(self, *a, **k) -> None:
        pass

    def record_stream_recovered(self, *a, **k) -> None:
        pass

    def set_attention_path(self, *a, **k) -> None:
        pass

    def set_engine_degraded(self, *a, **k) -> None:
        pass

    def set_pool_healthy(self, *a, **k) -> None:
        pass

    def record_probe_ejection(self, *a, **k) -> None:
        pass

    def record_probe_readmission(self, *a, **k) -> None:
        pass

    def record_affinity_hit(self, *a, **k) -> None:
        pass

    def record_affinity_spill(self, *a, **k) -> None:
        pass

    def record_stream_migrated(self, *a, **k) -> None:
        pass

    def set_deployment_load(self, *a, **k) -> None:
        pass

    def record_constrained_request(self, *a, **k) -> None:
        pass

    def record_schema_compile(self, *a, **k) -> None:
        pass

    def record_compile(self, *a, **k) -> None:
        pass

    def record_transfer(self, *a, **k) -> None:
        pass

    def set_hbm_bytes(self, *a, **k) -> None:
        pass

    def remove_hbm_gauges(self, *a, **k) -> None:
        pass
