"""Environment-variable configuration.

Capability parity with reference config/config.go:20-139: the same env-var
surface (ENVIRONMENT, ALLOWED_MODELS/DISALLOWED_MODELS, ENABLE_VISION,
TELEMETRY_*, MCP_*, AUTH_*, SERVER_*, CLIENT_*, ROUTING_*, plus per-provider
``<ID>_API_URL`` / ``<ID>_API_KEY``), the same defaults, and the same
"provider is not configured" notice for providers missing a token.

Like the reference's ``envconfig.Lookuper``, ``Config.load`` takes any
mapping (default ``os.environ``) so tests can inject environments without
touching the process env.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Mapping

from inference_gateway_tpu.providers import constants
from inference_gateway_tpu.providers.registry import REGISTRY, ProviderConfig
from inference_gateway_tpu.utils.durations import parse_duration


def _get_bool(env: Mapping[str, str], key: str, default: bool) -> bool:
    raw = env.get(key)
    if raw is None or raw == "":
        return default
    return raw.strip().lower() in ("1", "t", "true", "yes", "on")


def _get_str(env: Mapping[str, str], key: str, default: str = "") -> str:
    val = env.get(key)
    return default if val is None else val


def _get_int(env: Mapping[str, str], key: str, default: int) -> int:
    raw = env.get(key)
    if raw is None or raw == "":
        return default
    return int(raw)


def _get_float(env: Mapping[str, str], key: str, default: float) -> float:
    raw = env.get(key)
    if raw is None or raw == "":
        return default
    return float(raw)


def _get_duration(env: Mapping[str, str], key: str, default: str) -> float:
    return parse_duration(env.get(key) or default)


@dataclass
class TelemetryConfig:
    """TELEMETRY_* (config.go:46-52), plus the performance-introspection
    surface (ISSUE 4): TELEMETRY_PROFILING_* (sampling profiler,
    event-loop watchdog, decode-step timeline) and
    TELEMETRY_SLOW_REQUEST_* (forensics thresholds; 0 disables a check).
    """

    enable: bool = False
    metrics_push_enable: bool = False
    metrics_port: str = "9464"
    tracing_enable: bool = False
    tracing_otlp_endpoint: str = "http://localhost:4318"
    access_log: bool = False
    access_log_tail: int = 256
    profiling_enable: bool = False
    profiling_continuous: bool = False
    profiling_hz: float = 29.0
    profiling_window: float = 10.0
    profiling_windows: int = 6
    profiling_max_stacks: int = 2048
    profiling_watchdog: bool = False
    profiling_watchdog_interval: float = 0.25
    profiling_watchdog_threshold: float = 0.1
    profiling_timeline_size: int = 512
    slow_request_ttft: float = 0.0
    slow_request_tpot: float = 0.0
    slow_request_total: float = 0.0
    slow_request_log_size: int = 64
    # Stream journeys (ISSUE 18): per-worker lifecycle rings published
    # into the cluster segment. On by default — the <5% p99 overhead
    # gate (bench_fleet_observability_overhead) is the contract.
    journey_enable: bool = True
    journey_slots: int = 64
    journey_slot_bytes: int = 4096
    journey_events: int = 32
    # Device observatory (ISSUE 19): compile/recompile ledger, live HBM
    # accounting, and the h2d/d2h transfer audit. On by default — off
    # removes the jit wrappers entirely (zero overhead).
    device_enable: bool = True
    device_ledger_size: int = 256

    @classmethod
    def load(cls, env: Mapping[str, str], prefix: str = "TELEMETRY_") -> "TelemetryConfig":
        return cls(
            enable=_get_bool(env, prefix + "ENABLE", False),
            metrics_push_enable=_get_bool(env, prefix + "METRICS_PUSH_ENABLE", False),
            metrics_port=_get_str(env, prefix + "METRICS_PORT", "9464"),
            tracing_enable=_get_bool(env, prefix + "TRACING_ENABLE", False),
            tracing_otlp_endpoint=_get_str(env, prefix + "TRACING_OTLP_ENDPOINT", "http://localhost:4318"),
            access_log=_get_bool(env, prefix + "ACCESS_LOG", False),
            access_log_tail=_get_int(env, prefix + "ACCESS_LOG_TAIL", 256),
            profiling_enable=_get_bool(env, prefix + "PROFILING_ENABLE", False),
            profiling_continuous=_get_bool(env, prefix + "PROFILING_CONTINUOUS", False),
            profiling_hz=_get_float(env, prefix + "PROFILING_HZ", 29.0),
            profiling_window=_get_duration(env, prefix + "PROFILING_WINDOW", "10s"),
            profiling_windows=_get_int(env, prefix + "PROFILING_WINDOWS", 6),
            profiling_max_stacks=_get_int(env, prefix + "PROFILING_MAX_STACKS", 2048),
            profiling_watchdog=_get_bool(env, prefix + "PROFILING_WATCHDOG", False),
            profiling_watchdog_interval=_get_duration(env, prefix + "PROFILING_WATCHDOG_INTERVAL", "250ms"),
            profiling_watchdog_threshold=_get_duration(env, prefix + "PROFILING_WATCHDOG_THRESHOLD", "100ms"),
            profiling_timeline_size=_get_int(env, prefix + "PROFILING_TIMELINE_SIZE", 512),
            slow_request_ttft=_get_duration(env, prefix + "SLOW_REQUEST_TTFT", "0s"),
            slow_request_tpot=_get_duration(env, prefix + "SLOW_REQUEST_TPOT", "0s"),
            slow_request_total=_get_duration(env, prefix + "SLOW_REQUEST_TOTAL", "0s"),
            slow_request_log_size=_get_int(env, prefix + "SLOW_REQUEST_LOG_SIZE", 64),
            journey_enable=_get_bool(env, prefix + "JOURNEY_ENABLE", True),
            journey_slots=_get_int(env, prefix + "JOURNEY_SLOTS", 64),
            journey_slot_bytes=_get_int(env, prefix + "JOURNEY_SLOT_BYTES", 4096),
            journey_events=_get_int(env, prefix + "JOURNEY_EVENTS", 32),
            device_enable=_get_bool(env, prefix + "DEVICE_ENABLE", True),
            device_ledger_size=_get_int(env, prefix + "DEVICE_LEDGER_SIZE", 256),
        )


@dataclass
class MCPConfig:
    """MCP_* (config.go:55-76). Durations are float seconds."""

    enable: bool = False
    expose: bool = False
    servers: str = ""
    include_tools: str = ""
    exclude_tools: str = ""
    client_timeout: float = 5.0
    dial_timeout: float = 3.0
    tls_handshake_timeout: float = 3.0
    response_header_timeout: float = 3.0
    expect_continue_timeout: float = 1.0
    request_timeout: float = 5.0
    max_retries: int = 3
    retry_interval: float = 5.0
    initial_backoff: float = 1.0
    enable_reconnect: bool = True
    reconnect_interval: float = 30.0
    polling_enable: bool = True
    polling_interval: float = 30.0
    polling_timeout: float = 5.0
    disable_healthcheck_logs: bool = True

    @classmethod
    def load(cls, env: Mapping[str, str], prefix: str = "MCP_") -> "MCPConfig":
        return cls(
            enable=_get_bool(env, prefix + "ENABLE", False),
            expose=_get_bool(env, prefix + "EXPOSE", False),
            servers=_get_str(env, prefix + "SERVERS"),
            include_tools=_get_str(env, prefix + "INCLUDE_TOOLS"),
            exclude_tools=_get_str(env, prefix + "EXCLUDE_TOOLS"),
            client_timeout=_get_duration(env, prefix + "CLIENT_TIMEOUT", "5s"),
            dial_timeout=_get_duration(env, prefix + "DIAL_TIMEOUT", "3s"),
            tls_handshake_timeout=_get_duration(env, prefix + "TLS_HANDSHAKE_TIMEOUT", "3s"),
            response_header_timeout=_get_duration(env, prefix + "RESPONSE_HEADER_TIMEOUT", "3s"),
            expect_continue_timeout=_get_duration(env, prefix + "EXPECT_CONTINUE_TIMEOUT", "1s"),
            request_timeout=_get_duration(env, prefix + "REQUEST_TIMEOUT", "5s"),
            max_retries=_get_int(env, prefix + "MAX_RETRIES", 3),
            retry_interval=_get_duration(env, prefix + "RETRY_INTERVAL", "5s"),
            initial_backoff=_get_duration(env, prefix + "INITIAL_BACKOFF", "1s"),
            enable_reconnect=_get_bool(env, prefix + "ENABLE_RECONNECT", True),
            reconnect_interval=_get_duration(env, prefix + "RECONNECT_INTERVAL", "30s"),
            polling_enable=_get_bool(env, prefix + "POLLING_ENABLE", True),
            polling_interval=_get_duration(env, prefix + "POLLING_INTERVAL", "30s"),
            polling_timeout=_get_duration(env, prefix + "POLLING_TIMEOUT", "5s"),
            disable_healthcheck_logs=_get_bool(env, prefix + "DISABLE_HEALTHCHECK_LOGS", True),
        )


@dataclass
class AuthConfig:
    """AUTH_* (config.go:79-84)."""

    enable: bool = False
    oidc_issuer: str = "http://keycloak:8080/realms/inference-gateway-realm"
    oidc_client_id: str = "inference-gateway-client"
    oidc_client_secret: str = ""

    @classmethod
    def load(cls, env: Mapping[str, str], prefix: str = "AUTH_") -> "AuthConfig":
        return cls(
            enable=_get_bool(env, prefix + "ENABLE", False),
            oidc_issuer=_get_str(env, prefix + "OIDC_ISSUER", cls.oidc_issuer),
            oidc_client_id=_get_str(env, prefix + "OIDC_CLIENT_ID", cls.oidc_client_id),
            oidc_client_secret=_get_str(env, prefix + "OIDC_CLIENT_SECRET"),
        )


@dataclass
class ServerConfig:
    """SERVER_* (config.go:87-95)."""

    host: str = "0.0.0.0"
    port: str = "8080"
    read_timeout: float = 30.0
    write_timeout: float = 30.0
    idle_timeout: float = 120.0
    tls_cert_path: str = ""
    tls_key_path: str = ""
    # Streaming fast path: coalesce SSE chunk writes into one transport
    # write per event-loop pass (wire bytes identical; off = one write
    # per frame, the pre-fast-path behavior kept for A/B benching).
    stream_coalesce: bool = True

    @classmethod
    def load(cls, env: Mapping[str, str], prefix: str = "SERVER_") -> "ServerConfig":
        return cls(
            host=_get_str(env, prefix + "HOST", "0.0.0.0"),
            port=_get_str(env, prefix + "PORT", "8080"),
            read_timeout=_get_duration(env, prefix + "READ_TIMEOUT", "30s"),
            write_timeout=_get_duration(env, prefix + "WRITE_TIMEOUT", "30s"),
            idle_timeout=_get_duration(env, prefix + "IDLE_TIMEOUT", "120s"),
            tls_cert_path=_get_str(env, prefix + "TLS_CERT_PATH"),
            tls_key_path=_get_str(env, prefix + "TLS_KEY_PATH"),
            stream_coalesce=_get_bool(env, prefix + "STREAM_COALESCE", True),
        )


@dataclass
class ClientConfig:
    """CLIENT_* (reference providers/client/client.go:26-35)."""

    timeout: float = 30.0
    max_idle_conns: int = 20
    max_idle_conns_per_host: int = 20
    idle_conn_timeout: float = 30.0
    tls_min_version: str = "TLS12"
    disable_compression: bool = True
    response_header_timeout: float = 10.0
    expect_continue_timeout: float = 1.0

    @classmethod
    def load(cls, env: Mapping[str, str], prefix: str = "CLIENT_") -> "ClientConfig":
        return cls(
            timeout=_get_duration(env, prefix + "TIMEOUT", "30s"),
            max_idle_conns=_get_int(env, prefix + "MAX_IDLE_CONNS", 20),
            max_idle_conns_per_host=_get_int(env, prefix + "MAX_IDLE_CONNS_PER_HOST", 20),
            idle_conn_timeout=_get_duration(env, prefix + "IDLE_CONN_TIMEOUT", "30s"),
            tls_min_version=_get_str(env, prefix + "TLS_MIN_VERSION", "TLS12"),
            disable_compression=_get_bool(env, prefix + "DISABLE_COMPRESSION", True),
            response_header_timeout=_get_duration(env, prefix + "RESPONSE_HEADER_TIMEOUT", "10s"),
            expect_continue_timeout=_get_duration(env, prefix + "EXPECT_CONTINUE_TIMEOUT", "1s"),
        )


@dataclass
class ResilienceConfig:
    """RESILIENCE_* — circuit breakers, retry/backoff, failover, and
    per-request deadline budgets (ISSUE 1). Durations are float seconds.
    When RESILIENCE_REQUEST_BUDGET is unset, ``Config.load`` couples the
    budget to CLIENT_TIMEOUT so operators who lengthened the upstream
    timeout (long generations) aren't silently capped at 30s."""

    enabled: bool = True
    breaker_failure_threshold: int = 5
    breaker_cooldown: float = 30.0
    breaker_half_open_probes: int = 1
    retry_max_attempts: int = 3
    retry_base_backoff: float = 0.1
    retry_max_backoff: float = 2.0
    request_budget: float = 30.0
    stream_idle_timeout: float = 60.0
    # Mid-stream recovery (ISSUE 7): streamed requests are retryable
    # until the first relayed byte — an upstream that dies pre-first-byte
    # fails over to the next pool candidate instead of erroring the
    # client. stream_retry_max bounds the re-establishment hops.
    stream_retry_enabled: bool = True
    stream_retry_max: int = 2
    # Post-first-byte continuation (ISSUE 9): a stream that dies AFTER
    # the first relayed byte re-establishes on the next
    # continuation-capable pool candidate with the generated-so-far
    # prefix (the sidecar re-prefills and samples the next NEW token) and
    # splices frames byte-identically. continuation_max_buffer bounds the
    # accumulated prefix; past it, continuation disarms for that stream.
    continuation_enabled: bool = True
    continuation_max_buffer: int = 1 << 20
    # Active pool health probing (ISSUE 9): a background prober GETs each
    # pool deployment's /health every probe_interval; probe_failures
    # consecutive failures eject the deployment (zero establishment
    # attempts) until a probe succeeds again.
    probe_enabled: bool = True
    probe_interval: float = 5.0
    probe_timeout: float = 2.0
    probe_failures: int = 3

    @classmethod
    def load(cls, env: Mapping[str, str], prefix: str = "RESILIENCE_") -> "ResilienceConfig":
        return cls(
            enabled=_get_bool(env, prefix + "ENABLED", True),
            breaker_failure_threshold=_get_int(env, prefix + "BREAKER_FAILURE_THRESHOLD", 5),
            breaker_cooldown=_get_duration(env, prefix + "BREAKER_COOLDOWN", "30s"),
            breaker_half_open_probes=_get_int(env, prefix + "BREAKER_HALF_OPEN_PROBES", 1),
            retry_max_attempts=_get_int(env, prefix + "RETRY_MAX_ATTEMPTS", 3),
            retry_base_backoff=_get_duration(env, prefix + "RETRY_BASE_BACKOFF", "100ms"),
            retry_max_backoff=_get_duration(env, prefix + "RETRY_MAX_BACKOFF", "2s"),
            request_budget=_get_duration(env, prefix + "REQUEST_BUDGET", "30s"),
            stream_idle_timeout=_get_duration(env, prefix + "STREAM_IDLE_TIMEOUT", "60s"),
            stream_retry_enabled=_get_bool(env, prefix + "STREAM_RETRY_ENABLED", True),
            stream_retry_max=_get_int(env, prefix + "STREAM_RETRY_MAX", 2),
            continuation_enabled=_get_bool(env, prefix + "CONTINUATION_ENABLED", True),
            continuation_max_buffer=_get_int(env, prefix + "CONTINUATION_MAX_BUFFER", 1 << 20),
            probe_enabled=_get_bool(env, prefix + "PROBE_ENABLED", True),
            probe_interval=_get_duration(env, prefix + "PROBE_INTERVAL", "5s"),
            probe_timeout=_get_duration(env, prefix + "PROBE_TIMEOUT", "2s"),
            probe_failures=_get_int(env, prefix + "PROBE_FAILURES", 3),
        )


@dataclass
class OverloadConfig:
    """OVERLOAD_* / DRAIN_* — admission control, priority load shedding,
    and graceful drain (ISSUE 2). Caps and queue depths are per endpoint
    class: "streaming" covers the chat-shaped generation endpoints whose
    responses hold slots for whole SSE streams; "buffered" covers
    everything else. ``shed_high_water`` is the fraction of a wait
    queue's capacity at which batch-priority work is shed;
    ``engine_depth_high_water`` (0 = off) does the same against a
    registered serving-engine scheduler depth probe."""

    enabled: bool = True
    max_concurrent_streaming: int = 128
    max_concurrent_buffered: int = 256
    queue_depth_streaming: int = 64
    queue_depth_buffered: int = 128
    queue_timeout: float = 5.0
    shed_high_water: float = 0.5
    engine_depth_high_water: int = 0
    drain_deadline: float = 30.0
    drain_retry_after: float = 1.0

    @classmethod
    def load(cls, env: Mapping[str, str]) -> "OverloadConfig":
        return cls(
            enabled=_get_bool(env, "OVERLOAD_ENABLED", True),
            max_concurrent_streaming=_get_int(env, "OVERLOAD_MAX_CONCURRENT_STREAMING", 128),
            max_concurrent_buffered=_get_int(env, "OVERLOAD_MAX_CONCURRENT_BUFFERED", 256),
            queue_depth_streaming=_get_int(env, "OVERLOAD_QUEUE_DEPTH_STREAMING", 64),
            queue_depth_buffered=_get_int(env, "OVERLOAD_QUEUE_DEPTH_BUFFERED", 128),
            queue_timeout=_get_duration(env, "OVERLOAD_QUEUE_TIMEOUT", "5s"),
            shed_high_water=_get_float(env, "OVERLOAD_SHED_HIGH_WATER", 0.5),
            engine_depth_high_water=_get_int(env, "OVERLOAD_ENGINE_DEPTH_HIGH_WATER", 0),
            drain_deadline=_get_duration(env, "DRAIN_DEADLINE", "30s"),
            drain_retry_after=_get_duration(env, "DRAIN_RETRY_AFTER", "1s"),
        )


@dataclass
class ServingConfig:
    """SERVING_* — TPU-sidecar data-plane knobs (read by both the
    standalone sidecar entry point and a co-hosted SidecarServer).

    ``emit_coalesce`` (SERVING_EMIT_COALESCE_MS, seconds internally):
    opt-in token-emit batching — tokens produced within the window (in
    practice: the same decode step) merge into one SSE frame. Trades a
    bounded bump in time-to-first-content for far fewer frames under
    fan-out; per-token TPOT metrics are recorded on the scheduler
    thread, before framing, so they are unaffected. 0 keeps the
    one-frame-per-token wire shape byte-identical.

    Serving-path fault tolerance (ISSUE 7): ``SERVING_PREEMPT_*`` arms
    KV-pressure preemption (deschedule-and-resume instead of failing on
    page exhaustion, bounded per request by the budget);
    ``SERVING_WATCHDOG_*`` configures the engine hang watchdog whose
    device-step deadline (multiplier × EWMA step time, floored at the
    min deadline) trips a supervised in-place engine restart."""

    emit_coalesce: float = 0.0
    preempt_enable: bool = True
    preempt_budget: int = 3
    preempt_high_water: float = 0.0
    watchdog_enable: bool = True
    watchdog_interval: float = 1.0
    watchdog_multiplier: float = 20.0
    watchdog_min_deadline: float = 60.0
    # Planned live migration (ISSUE 11): drain/restart end live SSE
    # streams at a token boundary with no terminal frame so a
    # continuation-capable gateway splices them onto another replica.
    # False restores terminal "error" frames on restart (and drain only
    # blocks new work) — the pre-fleet contract for bare clients.
    migrate_streams: bool = True
    # The sidecar /admin/* surface (drain/undrain/migration record) is
    # unauthenticated like the rest of the listener; false removes the
    # routes for deployments exposed beyond the gateway network.
    admin_enabled: bool = True
    # Ragged mixed-step serving (ISSUE 12): one kernel launch per engine
    # step for any prefill/decode mix, so chunked prefill interleaves
    # with decode (no prefill head-of-line blocking) and paged engines
    # admit prompts up to the context window. Applies where the engine
    # supports it (paged, non-speculative, dense family); tokens is the
    # packed query budget per step — 0 = auto (largest prefill bucket +
    # max_slots).
    mixed_step_enable: bool = True
    mixed_step_tokens: int = 0
    # Desynchronized decode (ISSUE 14): on-device stopping, early-exit
    # fused chunks, host-free chained submits. decode_chunk/pipeline
    # depth 0 = keep the engine defaults (8 / 2); much larger chunks
    # are safe — finished slots freeze on device, so a long chunk pays
    # no wasted steps per finish the device detects.
    decode_chunk: int = 0
    decode_pipeline_depth: int = 0

    @classmethod
    def load(cls, env: Mapping[str, str], prefix: str = "SERVING_") -> "ServingConfig":
        # The _MS suffix promises milliseconds: a bare number is taken as
        # ms (unlike every other duration knob, where bare = seconds);
        # Go-style strings ("5ms", "0.01s") parse as written.
        raw = (env.get(prefix + "EMIT_COALESCE_MS") or "0s").strip()
        try:
            coalesce = float(raw) / 1000.0
        except ValueError:
            coalesce = parse_duration(raw)
        return cls(
            emit_coalesce=coalesce,
            preempt_enable=_get_bool(env, prefix + "PREEMPT_ENABLE", True),
            preempt_budget=_get_int(env, prefix + "PREEMPT_BUDGET", 3),
            preempt_high_water=_get_float(env, prefix + "PREEMPT_HIGH_WATER", 0.0),
            watchdog_enable=_get_bool(env, prefix + "WATCHDOG_ENABLE", True),
            watchdog_interval=_get_duration(env, prefix + "WATCHDOG_INTERVAL", "1s"),
            watchdog_multiplier=_get_float(env, prefix + "WATCHDOG_MULTIPLIER", 20.0),
            watchdog_min_deadline=_get_duration(env, prefix + "WATCHDOG_MIN_DEADLINE", "60s"),
            migrate_streams=_get_bool(env, prefix + "MIGRATE_STREAMS", True),
            admin_enabled=_get_bool(env, prefix + "ADMIN_ENABLED", True),
            mixed_step_enable=_get_bool(env, prefix + "MIXED_STEP_ENABLE", True),
            mixed_step_tokens=_get_int(env, prefix + "MIXED_STEP_TOKENS", 0),
            decode_chunk=_get_int(env, prefix + "DECODE_CHUNK", 0),
            decode_pipeline_depth=_get_int(env, prefix + "DECODE_PIPELINE_DEPTH", 0),
        )


@dataclass
class StructuredConfig:
    """STRUCTURED_* — grammar-constrained decoding (ISSUE 13): the TPU
    sidecar's structured-outputs subsystem (response_format json_object /
    json_schema lowered onto device-resident token-mask automaton tables,
    plus the logit_bias additive-bias buffer). ``max_states`` is the
    shared device-table budget in automaton states — transition-table
    memory is max_states x vocab x 4 bytes, so size it consciously for
    100k-token vocabularies; the tables only materialize on the first
    constrained request."""

    enable: bool = True
    cache_size: int = 64
    max_schema_bytes: int = 65536
    max_states: int = 4096

    @classmethod
    def load(cls, env: Mapping[str, str], prefix: str = "STRUCTURED_") -> "StructuredConfig":
        return cls(
            enable=_get_bool(env, prefix + "ENABLE", True),
            cache_size=_get_int(env, prefix + "CACHE_SIZE", 64),
            max_schema_bytes=_get_int(env, prefix + "MAX_SCHEMA_BYTES", 65536),
            max_states=_get_int(env, prefix + "MAX_STATES", 4096),
        )


@dataclass
class RoutingConfig:
    """ROUTING_* (config.go:98-101), plus the fleet-router surface
    (ISSUE 11): prefix-affinity consistent-hash routing over pool
    deployments (``AFFINITY_*``) and the bounded-load spill thresholds
    (``SPILL_*``) fed by the /health load reports the prober collects."""

    enabled: bool = False
    config_path: str = ""
    affinity_enabled: bool = True
    affinity_prefix_bytes: int = 1024
    affinity_vnodes: int = 64
    spill_queue_depth: int = 4
    spill_kv_high_water: float = 0.9

    @classmethod
    def load(cls, env: Mapping[str, str], prefix: str = "ROUTING_") -> "RoutingConfig":
        return cls(
            enabled=_get_bool(env, prefix + "ENABLED", False),
            config_path=_get_str(env, prefix + "CONFIG_PATH"),
            affinity_enabled=_get_bool(env, prefix + "AFFINITY_ENABLED", True),
            affinity_prefix_bytes=_get_int(env, prefix + "AFFINITY_PREFIX_BYTES", 1024),
            affinity_vnodes=_get_int(env, prefix + "AFFINITY_VNODES", 64),
            spill_queue_depth=_get_int(env, prefix + "SPILL_QUEUE_DEPTH", 4),
            spill_kv_high_water=_get_float(env, prefix + "SPILL_KV_HIGH_WATER", 0.9),
        )


@dataclass
class ClusterConfig:
    """CLUSTER_* — multi-worker scale-out (ISSUE 16). ``workers`` is the
    fleet size: 1 (the default) is today's single-process mode,
    byte-identical — no supervisor, no shared segment, no extra
    syscalls; > 1 forks that many gateway workers onto SO_REUSEPORT
    listeners under a crash supervisor. ``segment_name`` /
    ``worker_index`` / ``generation`` are the supervisor→worker
    handshake (set in each worker's environment at spawn, never by
    operators)."""

    workers: int = 1
    heartbeat_interval: float = 1.0
    heartbeat_timeout: float = 5.0
    boot_timeout: float = 30.0
    check_interval: float = 0.5
    tenant_slots: int = 64
    segment_name: str = ""
    worker_index: int = -1
    generation: int = 0

    @classmethod
    def load(cls, env: Mapping[str, str], prefix: str = "CLUSTER_") -> "ClusterConfig":
        return cls(
            workers=_get_int(env, prefix + "WORKERS", 1),
            heartbeat_interval=_get_duration(env, prefix + "HEARTBEAT_INTERVAL", "1s"),
            heartbeat_timeout=_get_duration(env, prefix + "HEARTBEAT_TIMEOUT", "5s"),
            boot_timeout=_get_duration(env, prefix + "BOOT_TIMEOUT", "30s"),
            check_interval=_get_duration(env, prefix + "CHECK_INTERVAL", "500ms"),
            tenant_slots=_get_int(env, prefix + "TENANT_SLOTS", 64),
            segment_name=_get_str(env, prefix + "SEGMENT_NAME"),
            worker_index=_get_int(env, prefix + "WORKER_INDEX", -1),
            generation=_get_int(env, prefix + "GENERATION", 0),
        )


@dataclass
class TenantConfig:
    """TENANT_* — per-tenant isolation at the admission edge (ISSUE 16):
    API-key/OIDC-derived tenant ids, weight-tiered quotas
    (``quota_base`` × weight = the tenant's cluster-wide in-flight cap;
    0 disables quotas), and fairness-weighted shedding under overload.
    ``weights`` is a ``tenant:weight`` comma list; unlisted tenants get
    ``default_weight``."""

    enabled: bool = False
    anonymous: str = "anonymous"
    default_weight: float = 1.0
    weights: str = ""
    quota_base: int = 0

    @classmethod
    def load(cls, env: Mapping[str, str], prefix: str = "TENANT_") -> "TenantConfig":
        return cls(
            enabled=_get_bool(env, prefix + "ENABLED", False),
            anonymous=_get_str(env, prefix + "ANONYMOUS", "anonymous"),
            default_weight=_get_float(env, prefix + "DEFAULT_WEIGHT", 1.0),
            weights=_get_str(env, prefix + "WEIGHTS"),
            quota_base=_get_int(env, prefix + "QUOTA_BASE", 0),
        )


@dataclass
class SLOConfig:
    """SLO_* — per-tenant / per-pool SLO accounting (ISSUE 18):
    sliding-window availability/TTFT/TPOT SLIs with multi-window (5m/1h)
    burn-rate gauges. ``*_target`` is the good-fraction objective
    (0.999 = three nines); ``*_threshold`` is the latency bound a
    request must beat to count good against the corresponding latency
    SLO. ``max_tenant_series`` bounds distinct tenant label values —
    the long tail folds into stable hashed ``overflow-N`` buckets."""

    enabled: bool = True
    availability_target: float = 0.999
    ttft_threshold: float = 2.0
    ttft_target: float = 0.99
    tpot_threshold: float = 0.25
    tpot_target: float = 0.99
    max_tenant_series: int = 64

    @classmethod
    def load(cls, env: Mapping[str, str], prefix: str = "SLO_") -> "SLOConfig":
        return cls(
            enabled=_get_bool(env, prefix + "ENABLED", True),
            availability_target=_get_float(env, prefix + "AVAILABILITY_TARGET", 0.999),
            ttft_threshold=_get_duration(env, prefix + "TTFT_THRESHOLD", "2s"),
            ttft_target=_get_float(env, prefix + "TTFT_TARGET", 0.99),
            tpot_threshold=_get_duration(env, prefix + "TPOT_THRESHOLD", "250ms"),
            tpot_target=_get_float(env, prefix + "TPOT_TARGET", 0.99),
            max_tenant_series=_get_int(env, prefix + "MAX_TENANT_SERIES", 64),
        )


@dataclass
class Config:
    """Top-level gateway configuration (config.go:20-43)."""

    environment: str = "production"
    allowed_models: str = ""
    disallowed_models: str = ""
    enable_vision: bool = False
    debug_content_truncate_words: int = 10
    debug_max_messages: int = 100
    telemetry: TelemetryConfig = field(default_factory=TelemetryConfig)
    mcp: MCPConfig = field(default_factory=MCPConfig)
    auth: AuthConfig = field(default_factory=AuthConfig)
    server: ServerConfig = field(default_factory=ServerConfig)
    client: ClientConfig = field(default_factory=ClientConfig)
    routing: RoutingConfig = field(default_factory=RoutingConfig)
    resilience: ResilienceConfig = field(default_factory=ResilienceConfig)
    overload: OverloadConfig = field(default_factory=OverloadConfig)
    serving: ServingConfig = field(default_factory=ServingConfig)
    structured: StructuredConfig = field(default_factory=StructuredConfig)
    cluster: ClusterConfig = field(default_factory=ClusterConfig)
    tenant: TenantConfig = field(default_factory=TenantConfig)
    slo: SLOConfig = field(default_factory=SLOConfig)
    providers: dict[str, ProviderConfig] = field(default_factory=dict)

    @classmethod
    def load(cls, env: Mapping[str, str] | None = None, logger=None) -> "Config":
        """Resolve config from an environment mapping
        (config.go:104-139)."""
        if env is None:
            env = os.environ
        cfg = cls(
            environment=_get_str(env, "ENVIRONMENT", "production"),
            allowed_models=_get_str(env, "ALLOWED_MODELS"),
            disallowed_models=_get_str(env, "DISALLOWED_MODELS"),
            enable_vision=_get_bool(env, "ENABLE_VISION", False),
            debug_content_truncate_words=_get_int(env, "DEBUG_CONTENT_TRUNCATE_WORDS", 10),
            debug_max_messages=_get_int(env, "DEBUG_MAX_MESSAGES", 100),
            telemetry=TelemetryConfig.load(env),
            mcp=MCPConfig.load(env),
            auth=AuthConfig.load(env),
            server=ServerConfig.load(env),
            client=ClientConfig.load(env),
            routing=RoutingConfig.load(env),
            resilience=ResilienceConfig.load(env),
            overload=OverloadConfig.load(env),
            serving=ServingConfig.load(env),
            structured=StructuredConfig.load(env),
            cluster=ClusterConfig.load(env),
            tenant=TenantConfig.load(env),
            slo=SLOConfig.load(env),
        )
        if not env.get("RESILIENCE_REQUEST_BUDGET"):
            # Follow the operator's upstream timeout unless the budget is
            # set explicitly (the spec default 30s == CLIENT_TIMEOUT's).
            cfg.resilience.request_budget = cfg.client.timeout
        for pid, defaults in REGISTRY.items():
            pc = defaults.copy()
            url = env.get(pid.upper() + "_API_URL")
            if url:
                pc.url = url
            token = env.get(pid.upper() + "_API_KEY", "")
            if not token and pc.auth_type != constants.AUTH_TYPE_NONE and logger is not None:
                logger.info("provider is not configured", "provider", pid)
            pc.token = token
            cfg.providers[pid] = pc
        return cfg
