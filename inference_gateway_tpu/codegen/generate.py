"""Spec-driven generation and drift guards.

Capability parity with the reference's codegen toolchain
(cmd/generate/main.go + internal/codegen + internal/mdgen +
internal/dockergen, orchestrated by `task generate`): openapi.yaml is the
single source of truth for the provider registry and the env-var config
surface. This CLI

- generates ``Configurations.md`` (env-var docs) from ``x-config``
- generates ``examples/docker-compose/basic/.env.example``
- verifies the in-code registry/constants/config against the spec
  (the reference's drift guards: provider_drift_test + CI dirty check)

Usage: ``python -m inference_gateway_tpu.codegen [-type MD|Env|Check|All]``
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

import yaml

REPO_ROOT = Path(__file__).resolve().parents[2]
SPEC_PATH = REPO_ROOT / "openapi.yaml"


def load_spec(path: Path = SPEC_PATH) -> dict:
    with open(path) as f:
        return yaml.safe_load(f)


# ---------------------------------------------------------------------------
# Generation
# ---------------------------------------------------------------------------
# Emitted under the Telemetry section of Configurations.md: the request
# observability surface (ISSUE 3 satellite) — what lights up when the
# TELEMETRY_* knobs are on, and where the dashboards live.
_TELEMETRY_OBSERVABILITY_DOC = [
    "### Request observability",
    "",
    "With `TELEMETRY_ENABLE` + `TELEMETRY_TRACING_ENABLE` on, one W3C trace",
    "spans the whole request: the gateway server span, the `/proxy` loopback",
    "hop, and the TPU sidecar's `queue.wait` / `prefill` / `decode` child",
    "spans (built from the scheduler's per-request phase clock). Token-level",
    "streaming histograms — inter-token latency (TPOT), time-in-queue, and",
    "output tokens/sec — record from the SSE relay and the scheduler emit",
    "path; engine gauges (batch-slot occupancy, KV-page utilization, queue",
    "depth, speculative acceptance) are sampled per request and per scrape.",
    "",
    "`TELEMETRY_ACCESS_LOG` emits one wide-event JSON line per request",
    "carrying the trace id, route, provider/model, status, token counts,",
    "phase durations, and shed/retry/failover annotations. The metrics",
    "listener serves `GET /metrics` (Prometheus) and `GET /debug/status`",
    "(JSON snapshot: build info, breaker states, admission ledger, live",
    "gauges). Span tree, instrument table, and example PromQL queries:",
    "[docs/observability.md](docs/observability.md).",
    "",
    "### Profiling & forensics",
    "",
    "`TELEMETRY_PROFILING_*` turns on the performance-introspection",
    "subsystem: a sampling wall-clock profiler with on-demand",
    "(`GET /debug/profile?seconds=N&hz=M`, flamegraph-ready collapsed",
    "stacks) and continuous (bounded ring of recent windows) modes, an",
    "event-loop stall watchdog (`eventloop.lag` histogram, stall counter,",
    "wide events carrying the loop thread's mid-stall stack), and the",
    "sidecar's engine decode-step timeline (`GET /debug/timeline`,",
    "`engine.step_duration` histogram). `TELEMETRY_SLOW_REQUEST_*`",
    "thresholds capture breaching requests — phase clock, trace id, and",
    "the surrounding engine-step window — into a bounded log surfaced in",
    "`/debug/status`. Everything is zero-overhead when off; how-tos",
    "(collapsed stacks → flamegraph.pl/speedscope, slow-request schema):",
    "[docs/observability.md](docs/observability.md).",
    "",
    "### Wasted work",
    "",
    "`engine.wasted_tokens{reason}` counts tokens the engine computed that",
    "no client saw (speculation rejections, chunk overrun, overshoot,",
    "disconnected clients, shed-after-prefill); the sidecar's",
    "`/debug/status` shows the same counts as `wasted_tokens`. Where the",
    "time goes on a chip is measured by `bench/` (see `PERF.md`), not",
    "estimated by the server. Schema and reading guide:",
    "[docs/observability.md](docs/observability.md).",
    "",
]


# Emitted under the Serving section of Configurations.md: the streaming
# data-plane fast path (ISSUE 5) in one paragraph.
_SERVING_DATA_PLANE_DOC = [
    "### Streaming data plane",
    "",
    "`SERVER_STREAM_COALESCE` (on by default) batches SSE chunk writes into",
    "one transport write per event-loop pass — client-visible bytes are",
    "identical, only the number of `send()` syscalls changes. The TPU",
    "sidecar serializes the chunk envelope once per request and splices",
    "per-token deltas in (no per-token `json.dumps`); its scheduler hands",
    "each decode step's tokens to the event loop in one wakeup.",
    "`SERVING_EMIT_COALESCE_MS` additionally merges same-step tokens into",
    "one frame — fewer chunks/s for a bounded time-to-first-content bump;",
    "per-token TPOT histograms are recorded before framing and are",
    "unaffected. Design + trade-offs: [docs/performance.md](docs/performance.md).",
    "",
]


# Emitted under the Serving section: ragged mixed-step scheduling
# (ISSUE 12) in one paragraph; design + tiling in docs/performance.md.
_SERVING_RAGGED_DOC = [
    "### Ragged mixed-step scheduling",
    "",
    "`SERVING_MIXED_STEP_ENABLE` (on by default for paged engines) serves",
    "each engine step as ONE ragged kernel launch over per-sequence",
    "(start, length) descriptors: decode rows and prefill-chunk rows share",
    "the step, so a long prompt's chunked prefill interleaves with active",
    "decode streams instead of serializing ahead of them, and paged",
    "engines admit prompts up to the context window. Greedy streams are",
    "byte-identical to the bucketed path. The dispatch verdict is exported",
    "as `engine.attention_path{path}` and `/debug/status.attention_path` —",
    "`gather` means the ~10.6×-slower GSPMD fallback is live (off-TPU",
    "only, post-ISSUE-12). Design: [docs/performance.md](docs/performance.md).",
    "",
    "A latent-family preset whose `num_nextn_predict_layers` is 1",
    "(`openpangu-ultra-moe-718b-stage7`) drafts for itself (ISSUE 56): its own",
    "next-token module drafts one token a row a round, and the mixed step and",
    "the decode chunk verify it as the row's second query and emit one or two",
    "tokens. No variable and no flag turns this on or off, `--spec-draft` is",
    "refused by name on such a preset, and `SERVING_DECODE_CHUNK` then counts",
    "ROUNDS. `/debug/status.mtp` and the `spec_*` counters say what the drafts",
    "came to ([docs/observability.md](docs/observability.md)).",
    "",
    "An EVA preset (`evabyte-6.5b`, ISSUE 61) keeps its page pool under rows of its",
    "own: a summary per 16 tokens of every window of 2,048 behind the current one,",
    "then that window's tokens. `SERVING_PAGE_SIZE` has to be window / chunk (128;",
    "another is refused by name), `SERVING_MAX_SEQ_LEN` 12,800 then costs a slot 21",
    "pages where a row a token would cost 100, prompt chunks end at a window's end",
    "whatever `SERVING_MIXED_STEP_TOKENS` says, and prefix hits are declined and",
    "counted. No variable and no flag turns any of it on or off",
    "([docs/observability.md](docs/observability.md), `eva`).",
    "",
    "A Jamba preset (`jamba2-3b`, ISSUE 64) keeps 10.1 MB of float32 state a slot",
    "(26 Mamba-1 mixers) beside a page pool of its TWO attention layers (1,024 B a",
    "cached token): `SERVING_MAX_SLOTS` sizes the store, `SERVING_MAX_SEQ_LEN` the",
    "pool alone. It is admitted through the mixed step whatever",
    "`SERVING_MIXED_STEP` says; a prompt chunk of `SERVING_MIXED_STEP_TOKENS` less",
    "the slots goes through the selective scan's chunk kernel and prefix hits are",
    "declined and counted. No variable and no flag turns any of it on or off",
    "([docs/observability.md](docs/observability.md), `ssm_scan`).",
    "",
]


# Emitted under the Serving section: the desynchronized decode steady
# state (ISSUE 14) in one paragraph; design in docs/performance.md.
_SERVING_DESYNC_DOC = [
    "### Host-free decode steady state",
    "",
    "The decode loop's control decisions are made on device: per-slot",
    "stop-token tables, max_tokens budgets, and the grammar accept-state",
    "ride the fused chunk carry, so finished slots freeze (no further",
    "sampling, KV writes masked) and the chunk exits its device loop the",
    "moment every slot is done — long `SERVING_DECODE_CHUNK` values pay no",
    "chunk-overrun waste, and chained chunk submits upload nothing (paged",
    "write indices are computed on device from a pre-reserved page horizon).",
    "Chunk N+1 is submitted before chunk N is fetched, and",
    "`SERVING_DECODE_PIPELINE_DEPTH` chunks (one by default) are left",
    "unfetched behind the running one,",
    "so the device never waits on the host between chunks and a request",
    "that finds a slot free waits out at most depth + 1 chunks in front of",
    "its prefill; the `engine.host_gap_ms`",
    "histogram and each `/debug/timeline` record's `host_gap_ms` measure",
    "the first, a mixed record's `prep.drained_steps` the second. Greedy and seeded streams are byte-identical at any chunk size;",
    "stop *strings* remain a host-side backstop that truncates after the",
    "fact. Design: [docs/performance.md](docs/performance.md).",
    "",
]


# Emitted under the Serving section: the serving-path fault model in one
# paragraph (ISSUE 7); the full story lives in docs/resilience.md.
_SERVING_FAULT_TOLERANCE_DOC = [
    "### Serving-path fault tolerance",
    "",
    "KV page exhaustion no longer fails requests: the scheduler *preempts*",
    "the youngest running request — its slot and pages are released and it",
    "re-enters the queue with prompt+generated-so-far for a recompute-style",
    "resume (PrefixCache makes the re-prefill cheap), emitting no duplicate",
    "and dropping no token. `SERVING_PREEMPT_BUDGET` bounds preemptions per",
    "request so livelock degrades to a clean failure. A wedged device step",
    "(`SERVING_WATCHDOG_*`) captures forensics, fails in-flight requests",
    "with a retryable error, rebuilds the engine in place, and flips health",
    "degraded→ready so failover pools route around the window. Full fault",
    "model: [docs/resilience.md](docs/resilience.md).",
    "",
]


# Emitted under the Resilience section of Configurations.md: what clients
# observe in each degraded mode (ISSUE 1 satellite).
_RESILIENCE_FAILURE_MODES = [
    "### Failure modes",
    "",
    "What a client sees when the resilience layer degrades a request:",
    "",
    "| Condition | HTTP status | Error envelope |",
    "|---|---|---|",
    "| Circuit open for the requested deployment; pool exhausted (every candidate open or failing) | `503` | `{\"error\": \"all deployments unavailable (circuit open)...\"}` (Messages API: `{\"type\": \"error\", \"error\": {\"type\": \"overloaded_error\", ...}}`) |",
    "| Deadline budget (`RESILIENCE_REQUEST_BUDGET`) exhausted across retries/failovers | `504` | `{\"error\": \"Request timed out\"}` |",
    "| Upstream kept failing after retries and failover (transport errors) | `502` | `{\"error\": \"<client error detail>\"}` |",
    "| Upstream returned a terminal HTTP error (passes through after retries for 429/5xx) | upstream status | upstream error body |",
    "| SSE relay idle past `RESILIENCE_STREAM_IDLE_TIMEOUT` | stream aborted mid-flight (headers already sent) — or transparently continued when the pool has a continuation-capable candidate | connection closed / spliced stream |",
    "",
    "### Stream continuation & active probing",
    "",
    "`RESILIENCE_CONTINUATION_*`: a streamed request whose upstream dies",
    "AFTER the first relayed byte no longer truncates the client stream —",
    "the gateway re-establishes on the next continuation-capable pool",
    "candidate with the generated-so-far prefix, the sidecar re-prefills",
    "prompt+prefix and samples the next NEW token (billing continuation",
    "tokens exactly once), and the frames are spliced so a greedy stream",
    "completes byte-identical to an unkilled run under one trace id.",
    "`RESILIENCE_PROBE_*`: a background health prober per pool deployment",
    "ejects dead replicas after K consecutive probe failures — ejected",
    "replicas get ZERO establishment attempts until a probe succeeds —",
    "with probe state in `/debug/status` and the",
    "`inference_gateway.pool_healthy` gauge. Full contract:",
    "[docs/resilience.md](docs/resilience.md).",
    "",
]


# Emitted under the Routing section of Configurations.md: the fleet
# data plane in one paragraph (ISSUE 11); details in docs/routing.md.
_ROUTING_FLEET_DOC = [
    "### Fleet routing",
    "",
    "With pools configured, the gateway routes by prompt-prefix affinity:",
    "the leading `ROUTING_AFFINITY_PREFIX_BYTES` of the message list hash",
    "onto a consistent-hash ring over the pool's deployments, so requests",
    "sharing a system prompt land where the sidecar's PrefixCache already",
    "holds their pages. An affine deployment whose `/health` load report",
    "says it is saturated (`ROUTING_SPILL_*`) is skipped for the next ring",
    "candidate (bounded load). Live streams migrate off a draining or",
    "restarting replica via the continuation splice",
    "(`POST /debug/fleet/drain?provider=&model=` on the metrics listener),",
    "and the cluster's reported backlog feeds admission control. Ring",
    "layout, key derivation, migration lifecycle, and pool-admission",
    "semantics: [docs/routing.md](docs/routing.md).",
    "",
]


# Emitted under the Overload section of Configurations.md: shed-order
# table + LB readiness semantics (ISSUE 2 satellite).
_OVERLOAD_DRAIN_DOC = [
    "### Overload & drain",
    "",
    "Admission control caps in-flight work per endpoint class and bounds the",
    "wait queue; excess is rejected with `429` + `Retry-After` computed from",
    "the observed per-class service time (monotone in the backlog). When any",
    "wait queue crosses `OVERLOAD_SHED_HIGH_WATER` — or a registered",
    "serving-engine depth probe crosses `OVERLOAD_ENGINE_DEPTH_HIGH_WATER` —",
    "the lowest-priority work is shed first with a sanitized `503`.",
    "",
    "Shed order (first shed to never shed):",
    "",
    "| Priority | Endpoints | Under overload | During drain |",
    "|---|---|---|---|",
    "| batch (shed first) | `GET /v1/models`, `GET /v1/mcp/tools`, `/proxy/*`, everything else | `503` shed | `503` + `Connection: close` |",
    "| interactive | `POST /v1/chat/completions`, `/v1/responses`, `/v1/messages` | queued up to the cap, then `429` + `Retry-After` | `503` + `Connection: close` |",
    "| critical (never shed) | `GET /health`, `GET /metrics`, `POST /v1/metrics` | always served | always served |",
    "",
    "LB readiness semantics: on SIGTERM the gateway flips readiness —",
    "`GET /health` returns `503 {\"message\": \"draining\"}` while the listener",
    "stays open. New non-critical requests are rejected fast; in-flight",
    "requests (including SSE streams, whose admission ticket is held until",
    "the last chunk) get `DRAIN_DEADLINE` to finish before sockets close.",
    "",
]


# Emitted under the Structured section of Configurations.md: the
# grammar-constrained decoding subsystem (ISSUE 13) in one paragraph.
_STRUCTURED_DOC = [
    "### Structured outputs",
    "",
    "`response_format` `json_object`/`json_schema` requests against the TPU",
    "sidecar compile the schema into a byte-level grammar and then into a",
    "token-mask automaton over the actual tokenizer vocabulary. The",
    "automaton's transition and packed-mask tables live in device memory, so",
    "constrained rows ride the same fused multi-step decode chunks, mixed",
    "steps, and speculative rounds as unconstrained traffic — each step",
    "applies the mask as an additive −inf bias before top-k/top-p and",
    "advances the state on device (no host sync mid-chunk). Compiled",
    "artifacts are cached by schema hash; uncompilable schemas fast-fail a",
    "structured 400 `code:unsupported_schema`. `logit_bias` rides the same",
    "additive-bias buffer. Supported schema subset, failure modes, and",
    "composition with speculation/continuation:",
    "[docs/structured-decoding.md](docs/structured-decoding.md).",
    "",
]


def generate_configurations_md(spec: dict) -> str:
    out = [
        "# Configurations",
        "",
        "_Generated from openapi.yaml `x-config` — do not edit by hand; run"
        " `python -m inference_gateway_tpu.codegen -type MD`._",
        "",
    ]
    for section, entries in spec["x-config"].items():
        out.append(f"## {section.title()}")
        out.append("")
        out.append("| Environment variable | Default | Description |")
        out.append("|---|---|---|")
        for e in entries:
            default = str(e.get("default", ""))
            out.append(f"| `{e['env']}` | `{default}` | {e['description']} |")
        out.append("")
        if section == "telemetry":
            out.extend(_TELEMETRY_OBSERVABILITY_DOC)
        elif section == "serving":
            out.extend(_SERVING_DATA_PLANE_DOC)
            out.extend(_SERVING_RAGGED_DOC)
            out.extend(_SERVING_DESYNC_DOC)
            out.extend(_SERVING_FAULT_TOLERANCE_DOC)
        elif section == "structured":
            out.extend(_STRUCTURED_DOC)
        elif section == "routing":
            out.extend(_ROUTING_FLEET_DOC)
        elif section == "resilience":
            out.extend(_RESILIENCE_FAILURE_MODES)
        elif section == "overload":
            out.extend(_OVERLOAD_DRAIN_DOC)
    out.append("## Providers")
    out.append("")
    out.append("| Provider | `<ID>_API_URL` default | Auth |")
    out.append("|---|---|---|")
    for pid, cfg in spec["x-provider-configs"].items():
        out.append(f"| {cfg['name']} | `{cfg['url']}` | {cfg['auth_type']} |")
    out.append("")
    return "\n".join(out)


def generate_env_example(spec: dict) -> str:
    lines = ["# Generated from openapi.yaml x-config — python -m inference_gateway_tpu.codegen -type Env", ""]
    for section, entries in spec["x-config"].items():
        lines.append(f"# --- {section} ---")
        for e in entries:
            lines.append(f"# {e['description']}")
            lines.append(f"{e['env']}={e.get('default', '')}")
        lines.append("")
    lines.append("# --- providers (API keys are required for non-local providers) ---")
    for pid, cfg in spec["x-provider-configs"].items():
        lines.append(f"# {pid.upper()}_API_URL={cfg['url']}")
        if cfg.get("auth_type") != "none":
            lines.append(f"# {pid.upper()}_API_KEY=")
    lines.append("")
    return "\n".join(lines)


def generate_constants_py(spec: dict) -> str:
    """providers/constants_gen.py — the spec-derived provider table.

    Parity with reference internal/codegen/codegen.go:222-659: constants
    and registry tables are GENERATED from openapi.yaml, so adding a
    provider is a spec-only change (`add to openapi.yaml + task generate
    is sufficient`). constants.py and registry.py derive their tables
    from this module; nothing provider-specific is hand-edited."""
    lines = [
        '"""GENERATED from openapi.yaml x-provider-configs — do not edit.',
        "",
        "Regenerate: ``python -m inference_gateway_tpu.codegen -type Code``.",
        "Drift-gated by ``-type Check`` (reference codegen.go:222-659 +",
        "CI dirty check).",
        '"""',
        "",
        "PROVIDER_TABLE = {",
    ]
    for pid, cfg in spec["x-provider-configs"].items():
        extra = {k: list(v) for k, v in (cfg.get("extra_headers") or {}).items()}
        lines.append(f"    {pid!r}: {{")
        lines.append(f"        \"name\": {cfg['name']!r},")
        lines.append(f"        \"url\": {cfg['url']!r},")
        lines.append(f"        \"auth_type\": {cfg['auth_type']!r},")
        lines.append(f"        \"supports_vision\": {bool(cfg.get('supports_vision', False))!r},")
        lines.append(f"        \"extra_headers\": {extra!r},")
        lines.append(
            f"        \"endpoints\": ({cfg['endpoints']['models']!r}, {cfg['endpoints']['chat']!r}),"
        )
        lines.append("    },")
    lines.append("}")
    lines.append("")
    lines.append("# Provider ID constants.")
    for pid in spec["x-provider-configs"]:
        lines.append(f"{pid.upper()}_ID = {pid!r}")
    lines.append("")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# Drift guards
# ---------------------------------------------------------------------------
def check_generated_code(spec: dict) -> list[str]:
    """Delete-and-regenerate must reproduce generated modules byte-identically."""
    from inference_gateway_tpu.codegen.typesgen import generate_types_py

    problems = []
    gen_path = REPO_ROOT / "inference_gateway_tpu" / "providers" / "constants_gen.py"
    want = generate_constants_py(spec)
    current = gen_path.read_text() if gen_path.exists() else ""
    if current != want:
        problems.append("providers/constants_gen.py drift — run codegen -type Code")
    types_path = REPO_ROOT / "inference_gateway_tpu" / "api" / "types_gen.py"
    want_types = generate_types_py(spec)
    current_types = types_path.read_text() if types_path.exists() else ""
    if current_types != want_types:
        problems.append("api/types_gen.py drift — run codegen -type Types")
    from inference_gateway_tpu.codegen.mcptypesgen import generate_mcp_types_py

    mcp_path = REPO_ROOT / "inference_gateway_tpu" / "mcp" / "types_gen.py"
    want_mcp = generate_mcp_types_py()
    current_mcp = mcp_path.read_text() if mcp_path.exists() else ""
    if current_mcp != want_mcp:
        problems.append("mcp/types_gen.py drift — run codegen -type Types")
    return problems
def check_provider_registry(spec: dict) -> list[str]:
    """Registry/constants must match x-provider-configs exactly."""
    from inference_gateway_tpu.providers import constants
    from inference_gateway_tpu.providers.registry import REGISTRY

    problems = []
    spec_providers = spec["x-provider-configs"]
    if set(spec_providers) != set(REGISTRY):
        problems.append(
            f"provider id sets differ: spec-only={set(spec_providers) - set(REGISTRY)}, "
            f"code-only={set(REGISTRY) - set(spec_providers)}"
        )
    for pid, s in spec_providers.items():
        cfg = REGISTRY.get(pid)
        if cfg is None:
            continue
        if cfg.name != s["name"]:
            problems.append(f"{pid}: name {cfg.name!r} != spec {s['name']!r}")
        if cfg.url != s["url"]:
            problems.append(f"{pid}: url {cfg.url!r} != spec {s['url']!r}")
        if cfg.auth_type != s["auth_type"]:
            problems.append(f"{pid}: auth_type {cfg.auth_type!r} != spec {s['auth_type']!r}")
        if cfg.supports_vision != s.get("supports_vision", False):
            problems.append(f"{pid}: supports_vision mismatch")
        if cfg.endpoints.models != s["endpoints"]["models"] or cfg.endpoints.chat != s["endpoints"]["chat"]:
            problems.append(f"{pid}: endpoints mismatch")
        spec_headers = {k: list(v) for k, v in (s.get("extra_headers") or {}).items()}
        if cfg.extra_headers != spec_headers:
            problems.append(f"{pid}: extra_headers mismatch")
        if constants.DEFAULT_BASE_URLS.get(pid) != s["url"]:
            problems.append(f"{pid}: constants.DEFAULT_BASE_URLS drift")
    # Every spec provider must transform (reference provider_drift_test).
    from inference_gateway_tpu.providers.transformers import transform_list_models

    for pid in spec_providers:
        try:
            transform_list_models(pid, {"data": [{"id": "x"}]})
        except Exception as e:
            problems.append(f"{pid}: transformer failed: {e}")
    return problems


def check_config_defaults(spec: dict) -> list[str]:
    """Config dataclass defaults must match x-config defaults."""
    from inference_gateway_tpu.config import Config
    from inference_gateway_tpu.utils.durations import parse_duration

    cfg = Config.load({})
    flat = {
        "ENVIRONMENT": cfg.environment,
        "ALLOWED_MODELS": cfg.allowed_models,
        "DISALLOWED_MODELS": cfg.disallowed_models,
        "ENABLE_VISION": cfg.enable_vision,
        "DEBUG_CONTENT_TRUNCATE_WORDS": cfg.debug_content_truncate_words,
        "DEBUG_MAX_MESSAGES": cfg.debug_max_messages,
        "TELEMETRY_ENABLE": cfg.telemetry.enable,
        "TELEMETRY_METRICS_PUSH_ENABLE": cfg.telemetry.metrics_push_enable,
        "TELEMETRY_METRICS_PORT": cfg.telemetry.metrics_port,
        "TELEMETRY_TRACING_ENABLE": cfg.telemetry.tracing_enable,
        "TELEMETRY_TRACING_OTLP_ENDPOINT": cfg.telemetry.tracing_otlp_endpoint,
        "TELEMETRY_ACCESS_LOG": cfg.telemetry.access_log,
        "TELEMETRY_ACCESS_LOG_TAIL": cfg.telemetry.access_log_tail,
        "TELEMETRY_PROFILING_ENABLE": cfg.telemetry.profiling_enable,
        "TELEMETRY_PROFILING_CONTINUOUS": cfg.telemetry.profiling_continuous,
        "TELEMETRY_PROFILING_HZ": cfg.telemetry.profiling_hz,
        "TELEMETRY_PROFILING_WINDOW": cfg.telemetry.profiling_window,
        "TELEMETRY_PROFILING_WINDOWS": cfg.telemetry.profiling_windows,
        "TELEMETRY_PROFILING_MAX_STACKS": cfg.telemetry.profiling_max_stacks,
        "TELEMETRY_PROFILING_WATCHDOG": cfg.telemetry.profiling_watchdog,
        "TELEMETRY_PROFILING_WATCHDOG_INTERVAL": cfg.telemetry.profiling_watchdog_interval,
        "TELEMETRY_PROFILING_WATCHDOG_THRESHOLD": cfg.telemetry.profiling_watchdog_threshold,
        "TELEMETRY_PROFILING_TIMELINE_SIZE": cfg.telemetry.profiling_timeline_size,
        "TELEMETRY_SLOW_REQUEST_TTFT": cfg.telemetry.slow_request_ttft,
        "TELEMETRY_SLOW_REQUEST_TPOT": cfg.telemetry.slow_request_tpot,
        "TELEMETRY_SLOW_REQUEST_TOTAL": cfg.telemetry.slow_request_total,
        "TELEMETRY_SLOW_REQUEST_LOG_SIZE": cfg.telemetry.slow_request_log_size,
        "TELEMETRY_DEVICE_ENABLE": cfg.telemetry.device_enable,
        "TELEMETRY_DEVICE_LEDGER_SIZE": cfg.telemetry.device_ledger_size,
        "TELEMETRY_JOURNEY_ENABLE": cfg.telemetry.journey_enable,
        "TELEMETRY_JOURNEY_SLOTS": cfg.telemetry.journey_slots,
        "TELEMETRY_JOURNEY_SLOT_BYTES": cfg.telemetry.journey_slot_bytes,
        "TELEMETRY_JOURNEY_EVENTS": cfg.telemetry.journey_events,
        "MCP_ENABLE": cfg.mcp.enable,
        "MCP_EXPOSE": cfg.mcp.expose,
        "MCP_SERVERS": cfg.mcp.servers,
        "MCP_INCLUDE_TOOLS": cfg.mcp.include_tools,
        "MCP_EXCLUDE_TOOLS": cfg.mcp.exclude_tools,
        "MCP_CLIENT_TIMEOUT": cfg.mcp.client_timeout,
        "MCP_DIAL_TIMEOUT": cfg.mcp.dial_timeout,
        "MCP_TLS_HANDSHAKE_TIMEOUT": cfg.mcp.tls_handshake_timeout,
        "MCP_RESPONSE_HEADER_TIMEOUT": cfg.mcp.response_header_timeout,
        "MCP_EXPECT_CONTINUE_TIMEOUT": cfg.mcp.expect_continue_timeout,
        "MCP_REQUEST_TIMEOUT": cfg.mcp.request_timeout,
        "MCP_MAX_RETRIES": cfg.mcp.max_retries,
        "MCP_RETRY_INTERVAL": cfg.mcp.retry_interval,
        "MCP_INITIAL_BACKOFF": cfg.mcp.initial_backoff,
        "MCP_ENABLE_RECONNECT": cfg.mcp.enable_reconnect,
        "MCP_RECONNECT_INTERVAL": cfg.mcp.reconnect_interval,
        "MCP_POLLING_ENABLE": cfg.mcp.polling_enable,
        "MCP_POLLING_INTERVAL": cfg.mcp.polling_interval,
        "MCP_POLLING_TIMEOUT": cfg.mcp.polling_timeout,
        "MCP_DISABLE_HEALTHCHECK_LOGS": cfg.mcp.disable_healthcheck_logs,
        "AUTH_ENABLE": cfg.auth.enable,
        "AUTH_OIDC_ISSUER": cfg.auth.oidc_issuer,
        "AUTH_OIDC_CLIENT_ID": cfg.auth.oidc_client_id,
        "AUTH_OIDC_CLIENT_SECRET": cfg.auth.oidc_client_secret,
        "SERVER_HOST": cfg.server.host,
        "SERVER_PORT": cfg.server.port,
        "SERVER_READ_TIMEOUT": cfg.server.read_timeout,
        "SERVER_WRITE_TIMEOUT": cfg.server.write_timeout,
        "SERVER_IDLE_TIMEOUT": cfg.server.idle_timeout,
        "SERVER_TLS_CERT_PATH": cfg.server.tls_cert_path,
        "SERVER_TLS_KEY_PATH": cfg.server.tls_key_path,
        "SERVER_STREAM_COALESCE": cfg.server.stream_coalesce,
        "SERVING_EMIT_COALESCE_MS": cfg.serving.emit_coalesce,
        "SERVING_PREEMPT_ENABLE": cfg.serving.preempt_enable,
        "SERVING_PREEMPT_BUDGET": cfg.serving.preempt_budget,
        "SERVING_PREEMPT_HIGH_WATER": cfg.serving.preempt_high_water,
        "SERVING_WATCHDOG_ENABLE": cfg.serving.watchdog_enable,
        "SERVING_WATCHDOG_INTERVAL": cfg.serving.watchdog_interval,
        "SERVING_WATCHDOG_MULTIPLIER": cfg.serving.watchdog_multiplier,
        "SERVING_WATCHDOG_MIN_DEADLINE": cfg.serving.watchdog_min_deadline,
        "SERVING_MIGRATE_STREAMS": cfg.serving.migrate_streams,
        "SERVING_ADMIN_ENABLED": cfg.serving.admin_enabled,
        "SERVING_MIXED_STEP_ENABLE": cfg.serving.mixed_step_enable,
        "SERVING_MIXED_STEP_TOKENS": cfg.serving.mixed_step_tokens,
        "SERVING_DECODE_CHUNK": cfg.serving.decode_chunk,
        "SERVING_DECODE_PIPELINE_DEPTH": cfg.serving.decode_pipeline_depth,
        "STRUCTURED_ENABLE": cfg.structured.enable,
        "STRUCTURED_CACHE_SIZE": cfg.structured.cache_size,
        "STRUCTURED_MAX_SCHEMA_BYTES": cfg.structured.max_schema_bytes,
        "STRUCTURED_MAX_STATES": cfg.structured.max_states,
        # Read at import by ops/paged_attention (FORCE_PAGED_KERNEL),
        # not through a Config dataclass — listed so the dispatch force
        # flag appears in Configurations.md/.env.example without this
        # check importing jax.
        "IG_TPU_PAGED_KERNEL": "",
        "CLIENT_TIMEOUT": cfg.client.timeout,
        "CLIENT_MAX_IDLE_CONNS": cfg.client.max_idle_conns,
        "CLIENT_MAX_IDLE_CONNS_PER_HOST": cfg.client.max_idle_conns_per_host,
        "CLIENT_IDLE_CONN_TIMEOUT": cfg.client.idle_conn_timeout,
        "CLIENT_TLS_MIN_VERSION": cfg.client.tls_min_version,
        "CLIENT_DISABLE_COMPRESSION": cfg.client.disable_compression,
        "CLIENT_RESPONSE_HEADER_TIMEOUT": cfg.client.response_header_timeout,
        "CLIENT_EXPECT_CONTINUE_TIMEOUT": cfg.client.expect_continue_timeout,
        "ROUTING_ENABLED": cfg.routing.enabled,
        "ROUTING_CONFIG_PATH": cfg.routing.config_path,
        "ROUTING_AFFINITY_ENABLED": cfg.routing.affinity_enabled,
        "ROUTING_AFFINITY_PREFIX_BYTES": cfg.routing.affinity_prefix_bytes,
        "ROUTING_AFFINITY_VNODES": cfg.routing.affinity_vnodes,
        "ROUTING_SPILL_QUEUE_DEPTH": cfg.routing.spill_queue_depth,
        "ROUTING_SPILL_KV_HIGH_WATER": cfg.routing.spill_kv_high_water,
        "RESILIENCE_ENABLED": cfg.resilience.enabled,
        "RESILIENCE_BREAKER_FAILURE_THRESHOLD": cfg.resilience.breaker_failure_threshold,
        "RESILIENCE_BREAKER_COOLDOWN": cfg.resilience.breaker_cooldown,
        "RESILIENCE_BREAKER_HALF_OPEN_PROBES": cfg.resilience.breaker_half_open_probes,
        "RESILIENCE_RETRY_MAX_ATTEMPTS": cfg.resilience.retry_max_attempts,
        "RESILIENCE_RETRY_BASE_BACKOFF": cfg.resilience.retry_base_backoff,
        "RESILIENCE_RETRY_MAX_BACKOFF": cfg.resilience.retry_max_backoff,
        "RESILIENCE_REQUEST_BUDGET": cfg.resilience.request_budget,
        "RESILIENCE_STREAM_IDLE_TIMEOUT": cfg.resilience.stream_idle_timeout,
        "RESILIENCE_STREAM_RETRY_ENABLED": cfg.resilience.stream_retry_enabled,
        "RESILIENCE_STREAM_RETRY_MAX": cfg.resilience.stream_retry_max,
        "RESILIENCE_CONTINUATION_ENABLED": cfg.resilience.continuation_enabled,
        "RESILIENCE_CONTINUATION_MAX_BUFFER": cfg.resilience.continuation_max_buffer,
        "RESILIENCE_PROBE_ENABLED": cfg.resilience.probe_enabled,
        "RESILIENCE_PROBE_INTERVAL": cfg.resilience.probe_interval,
        "RESILIENCE_PROBE_TIMEOUT": cfg.resilience.probe_timeout,
        "RESILIENCE_PROBE_FAILURES": cfg.resilience.probe_failures,
        "OVERLOAD_ENABLED": cfg.overload.enabled,
        "OVERLOAD_MAX_CONCURRENT_STREAMING": cfg.overload.max_concurrent_streaming,
        "OVERLOAD_MAX_CONCURRENT_BUFFERED": cfg.overload.max_concurrent_buffered,
        "OVERLOAD_QUEUE_DEPTH_STREAMING": cfg.overload.queue_depth_streaming,
        "OVERLOAD_QUEUE_DEPTH_BUFFERED": cfg.overload.queue_depth_buffered,
        "OVERLOAD_QUEUE_TIMEOUT": cfg.overload.queue_timeout,
        "OVERLOAD_SHED_HIGH_WATER": cfg.overload.shed_high_water,
        "OVERLOAD_ENGINE_DEPTH_HIGH_WATER": cfg.overload.engine_depth_high_water,
        "DRAIN_DEADLINE": cfg.overload.drain_deadline,
        "DRAIN_RETRY_AFTER": cfg.overload.drain_retry_after,
        "CLUSTER_WORKERS": cfg.cluster.workers,
        "CLUSTER_HEARTBEAT_INTERVAL": cfg.cluster.heartbeat_interval,
        "CLUSTER_HEARTBEAT_TIMEOUT": cfg.cluster.heartbeat_timeout,
        "CLUSTER_BOOT_TIMEOUT": cfg.cluster.boot_timeout,
        "CLUSTER_CHECK_INTERVAL": cfg.cluster.check_interval,
        "CLUSTER_TENANT_SLOTS": cfg.cluster.tenant_slots,
        "CLUSTER_SEGMENT_NAME": cfg.cluster.segment_name,
        "CLUSTER_WORKER_INDEX": cfg.cluster.worker_index,
        "CLUSTER_GENERATION": cfg.cluster.generation,
        "TENANT_ENABLED": cfg.tenant.enabled,
        "TENANT_ANONYMOUS": cfg.tenant.anonymous,
        "TENANT_DEFAULT_WEIGHT": cfg.tenant.default_weight,
        "TENANT_WEIGHTS": cfg.tenant.weights,
        "TENANT_QUOTA_BASE": cfg.tenant.quota_base,
        "SLO_ENABLED": cfg.slo.enabled,
        "SLO_AVAILABILITY_TARGET": cfg.slo.availability_target,
        "SLO_TTFT_THRESHOLD": cfg.slo.ttft_threshold,
        "SLO_TTFT_TARGET": cfg.slo.ttft_target,
        "SLO_TPOT_THRESHOLD": cfg.slo.tpot_threshold,
        "SLO_TPOT_TARGET": cfg.slo.tpot_target,
        "SLO_MAX_TENANT_SERIES": cfg.slo.max_tenant_series,
    }
    problems = []
    seen = set()
    for section, entries in spec["x-config"].items():
        for e in entries:
            env = e["env"]
            seen.add(env)
            if env not in flat:
                problems.append(f"{env}: in spec but not loaded by Config")
                continue
            actual = flat[env]
            want = e.get("default", "")
            if isinstance(actual, bool):
                want_b = str(want).strip().lower() in ("1", "t", "true", "yes", "on")
                ok = actual == want_b
            elif isinstance(actual, (int,)) and not isinstance(actual, bool):
                ok = str(actual) == str(want)
            elif isinstance(actual, float):
                ok = abs(actual - parse_duration(str(want))) < 1e-9
            else:
                ok = str(actual) == str(want)
            if not ok:
                problems.append(f"{env}: code default {actual!r} != spec default {want!r}")
    missing = set(flat) - seen
    if missing:
        problems.append(f"Config fields missing from spec: {sorted(missing)}")
    return problems


# ---------------------------------------------------------------------------
def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description="spec-driven generation + drift guards")
    parser.add_argument("-type", dest="gen_type", default="All",
                        choices=["MD", "Env", "Code", "Types", "Check", "All"])
    args = parser.parse_args(argv)
    spec = load_spec()

    if args.gen_type in ("Code", "All"):
        target = REPO_ROOT / "inference_gateway_tpu" / "providers" / "constants_gen.py"
        target.write_text(generate_constants_py(spec))
        print(f"wrote {target.relative_to(REPO_ROOT)}")
    if args.gen_type in ("Types", "All"):
        from inference_gateway_tpu.codegen.mcptypesgen import generate_mcp_types_py
        from inference_gateway_tpu.codegen.typesgen import generate_types_py

        target = REPO_ROOT / "inference_gateway_tpu" / "api" / "types_gen.py"
        target.write_text(generate_types_py(spec))
        print(f"wrote {target.relative_to(REPO_ROOT)}")
        target = REPO_ROOT / "inference_gateway_tpu" / "mcp" / "types_gen.py"
        target.write_text(generate_mcp_types_py())
        print(f"wrote {target.relative_to(REPO_ROOT)}")
    if args.gen_type in ("MD", "All"):
        (REPO_ROOT / "Configurations.md").write_text(generate_configurations_md(spec))
        print("wrote Configurations.md")
    if args.gen_type in ("Env", "All"):
        target = REPO_ROOT / "examples" / "docker-compose" / "basic" / ".env.example"
        target.parent.mkdir(parents=True, exist_ok=True)
        target.write_text(generate_env_example(spec))
        print(f"wrote {target.relative_to(REPO_ROOT)}")
    if args.gen_type in ("Check", "All"):
        problems = (check_generated_code(spec) + check_provider_registry(spec)
                    + check_config_defaults(spec))
        # Community tables are part of the same `task generate` contract.
        from inference_gateway_tpu.codegen import pricinggen

        if pricinggen.run("check") != 0:
            problems.append("community tables drift — run codegen.pricinggen --write")
        if problems:
            print("DRIFT DETECTED:")
            for p in problems:
                print(" -", p)
            return 1
        print("drift check: clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())
