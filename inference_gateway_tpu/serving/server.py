"""The TPU serving sidecar: an OpenAI-compatible HTTP server over the
continuous-batching engine.

This is the upstream behind the gateway's first-class ``tpu`` provider —
the same contract llama.cpp/Ollama fulfil for the reference
(providers/registry/registry.go:143-208):

- ``GET  /v1/models``            — OpenAI list-models shape
- ``POST /v1/chat/completions``  — non-streaming + SSE streaming with
  OpenAI-chunk-exact framing (usage in the trailing chunks, then
  ``data: [DONE]``) so the gateway's telemetry middleware and MCP agent
  parse it unchanged (SURVEY.md §7 "streaming fidelity").
- ``GET  /props``                — llama.cpp-compatible runtime metadata
  (default_generation_settings.n_ctx) feeding the gateway's runtime
  context-window tier (reference api/context_window.go:86-100).
- ``GET  /health``, ``GET /metrics`` — liveness + engine counters
  (tokens/sec, queue depth, TTFT) for observability.

Tokens stream straight off the decode loop: the scheduler thread pushes
sampled tokens into an asyncio queue consumed by the SSE writer.
"""

from __future__ import annotations

import asyncio
import json
import time
import uuid
from typing import Any

from inference_gateway_tpu.logger import Logger, new_logger
from inference_gateway_tpu.netio import sse
from inference_gateway_tpu.netio.server import HTTPServer, Request, Response, Router, StreamingResponse
from inference_gateway_tpu.otel.device_observatory import DeviceObservatory
from inference_gateway_tpu.otel.profiling import (
    SlowRequestLog,
    StepTimeline,
    handle_profile_query,
    jax_trace_capture,
)
from inference_gateway_tpu.otel.tracing import Tracer, parse_traceparent
from inference_gateway_tpu.resilience.clock import MonotonicClock
from inference_gateway_tpu.resilience.overload import ServiceTimeEstimator
from inference_gateway_tpu.serving.engine import Engine, EngineConfig
from inference_gateway_tpu.serving.scheduler import (
    GenRequest,
    Scheduler,
    SchedulerSaturatedError,
    SchedulerStoppedError,
)
from inference_gateway_tpu.serving.tokenizer import DetokenizeState
from inference_gateway_tpu.utils.platform import configure_compile_cache

# OTLP push bucket boundaries (delta histograms; the gateway ingest
# replays observations at bucket midpoints).
_PUSH_TTFT_BOUNDS = [0.01, 0.025, 0.05, 0.1, 0.2, 0.4, 0.8, 1.6, 3.2, 6.4]
_PUSH_TPOT_BOUNDS = [0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0]
# Cap on each pending-push sample list: with no push URL configured
# nothing drains them, and a long-lived replica appending one float per
# generated token must not grow without bound (review finding). 64k
# samples ≈ far more than any push interval accumulates.
_MAX_PENDING_SAMPLES = 65536

# Per-token JSON string escaping for the template-based SSE fast path —
# the exact escaper json.dumps(ensure_ascii=True) uses (C-accelerated),
# so spliced frames stay byte-identical to full-envelope serialization.
_json_escape = json.encoder.encode_basestring_ascii

# Queue sentinel for planned live migration (ISSUE 11): drain/restart
# inject ``(_MIGRATE, reason)`` into a live stream's token queue, ending
# the SSE generator at the current frame boundary with NO terminal frame
# — the exact death shape the gateway's continuation splice (PR 9)
# resumes byte-identically on another replica. A bare (non-gateway)
# client sees a truncated stream (missing [DONE]), which the OpenAI wire
# shape defines as detectable.
_MIGRATE = object()


def _migrate_signal(item: object) -> str | None:
    """The migration reason when ``item`` is the sentinel, else None
    (regular queue items are LISTS of token tuples, never tuples)."""
    if isinstance(item, tuple) and len(item) == 2 and item[0] is _MIGRATE:
        return str(item[1])
    return None


class _BadRequest(Exception):
    """A structured 400 raised during request preparation (ISSUE 13):
    unsupported response_format schemas and invalid logit_bias fast-fail
    BEFORE any slot or KV page is allocated — the ``prompt_too_long``
    pattern."""

    def __init__(self, message: str, code: str, param: str,
                 extra: dict[str, Any] | None = None) -> None:
        super().__init__(message)
        self.payload = {"error": {
            "message": message,
            "type": "invalid_request_error",
            "param": param,
            "code": code,
            **(extra or {}),
        }}


class SidecarServer:
    def __init__(self, engine: Engine, scheduler: Scheduler | None = None,
                 served_model_name: str | None = None, logger: Logger | None = None,
                 metrics_push_url: str | None = None, metrics_push_interval: float = 15.0,
                 max_queue_depth: int = 0, tracer: Tracer | None = None,
                 otel=None, access_log=None, timeline: StepTimeline | None = None,
                 timeline_size: int = 512, slow_log: SlowRequestLog | None = None,
                 profiler=None, watchdog=None, emit_coalesce: float = 0.0,
                 stream_coalesce: bool = True,
                 observatory: DeviceObservatory | None = None,
                 device_enable: bool = True,
                 device_ledger_size: int = 256,
                 preempt_max: int = 3, preempt_high_water: float = 0.0,
                 engine_watchdog=None, engine_factory=None, clock=None,
                 migrate_streams: bool = True, admin_enabled: bool = True):
        self.engine = engine
        self.logger = logger or new_logger()
        # Injectable monotonic clock (graftlint clock-discipline): all
        # duration math (uptime, service time, health staleness) reads
        # through it; shared with the scheduler — adopted FROM an
        # externally-passed scheduler, passed INTO one built here — so
        # the two sides of the last_step_time staleness comparison can
        # never use different timebases. Epoch wire-format stamps
        # (``created``) stay on real wall-clock.
        self._clock = clock or (getattr(scheduler, "clock", None)
                                if scheduler is not None else None) or MonotonicClock()
        # Serving-path fault tolerance (ISSUE 7): "ok" | "degraded" —
        # degraded flips /health to 503 while a supervised engine
        # restart is in flight, so PR 1 failover pools route around the
        # window. engine_factory rebuilds the Engine in place (default:
        # same config, fresh weights/caches); engine_watchdog (an
        # EngineWatchdog) trips the restart on a wedged device step.
        self.state = "ok"
        self.restarts = 0
        self.last_restart: dict[str, Any] | None = None
        # Planned live migration (ISSUE 11): live SSE streams tracked so
        # a drain (or supervised restart) can end each one at a token
        # boundary with no terminal frame — the continuation-capable
        # gateway splices them onto another replica. migrate_streams=False
        # restores the pre-fleet contract (restart fails streams with a
        # terminal "error" frame; drain only blocks new work).
        self.migrate_streams = migrate_streams
        # The /admin/* surface (drain/undrain/migration) is mutating and
        # unauthenticated like the rest of this listener: it assumes the
        # sidecar port is reachable only from the gateway network (the
        # same trust model as /v1/chat/completions, which is equally
        # open). SERVING_ADMIN_ENABLED=false removes the routes for
        # deployments that expose the sidecar more widely.
        self.admin_enabled = admin_enabled
        # Drain intent, separate from ``state``: a drain requested while
        # a supervised restart is in flight ("degraded") must survive
        # the restart's completion instead of being clobbered back to
        # "ok" (code-review finding). ``state`` stays the single
        # externally-visible verdict; this flag is what restart
        # completion restores it from.
        self._drain_requested = False
        self._active_streams: dict[str, tuple[GenRequest, asyncio.Queue]] = {}
        self.migrated_out = 0
        # Authoritative resume material per migrated stream (ISSUE 11):
        # completion id -> {token_ids, reason}. The gateway's
        # continuation holds only TEXT (frames carry no ids), and text
        # re-encoding is lossy when the cut lands mid-UTF-8 or mid-merge
        # — but a PLANNED migration leaves this replica alive, so it
        # publishes the exact prompt-relative generated ids + the reason
        # (GET /admin/migration?id=...) and the new replica resumes
        # byte-identically from them. Bounded FIFO.
        self._migration_resume: dict[str, dict[str, Any]] = {}
        self.engine_factory = engine_factory
        self.engine_watchdog = engine_watchdog
        self.preempt_max = preempt_max
        self.preempt_high_water = preempt_high_water
        # Observability wiring (ISSUE 3): a tracer for the sidecar's
        # queue.wait/prefill/decode child spans (disabled by default —
        # spans are built only when enabled), an optional co-hosted
        # OpenTelemetry facade whose Registry receives queue-wait/TPOT
        # histograms and engine gauges directly (the cross-process path
        # is the OTLP push loop below), and an optional wide-event
        # access log (one JSON line per request with phase durations).
        self.tracer = tracer or Tracer("tpu-sidecar", enabled=False)
        self.otel = otel
        self.access_log = access_log
        # The scheduler's failure paths log through this logger —
        # without it a recurring _admit/_release bug would be invisible
        # in the deployed sidecar (round-3 review finding).
        self.scheduler = scheduler or Scheduler(engine, logger=self.logger,
                                                max_queue_depth=max_queue_depth,
                                                preempt_max=preempt_max,
                                                preempt_high_water=preempt_high_water,
                                                clock=self._clock)
        self._own_scheduler = scheduler is None
        if self.scheduler.on_preempt is None:
            self.scheduler.on_preempt = self._on_preempt
        if self.engine_watchdog is not None:
            self.engine_watchdog.bind(self)
        # Observed per-request service time → Retry-After hints when the
        # scheduler queue saturates (ISSUE 2; same estimator as the
        # gateway's admission ledger so the policy can't drift).
        self._service = ServiceTimeEstimator()
        self.model_name = served_model_name or engine.config.model
        self.created = int(time.time())  # graftlint: disable=clock-discipline -- epoch stamp for the /v1/models wire format
        self._started = self._clock.now()
        # Performance introspection (ISSUE 4): a decode-step timeline on
        # the scheduler thread (GET /debug/timeline; timeline_size=0
        # disables), slow-request forensics fed by the phase clock in
        # _finalize_request, and optional sampling profiler / event-loop
        # watchdog instances owned by serve() in the standalone sidecar.
        if timeline is None and timeline_size > 0:
            timeline = StepTimeline(timeline_size, otel=otel, model=self.model_name)
        self.timeline = timeline
        if self.scheduler.timeline is None:
            self.scheduler.timeline = timeline
        if slow_log is not None and slow_log.timeline is None:
            slow_log.timeline = timeline
        self.slow_log = slow_log
        self.profiler = profiler
        self.watchdog = watchdog
        # Set by serve() (start-up spans) and by /debug/jax_trace (the
        # last capture's place on the span clock); both ride /debug/status.
        self.startup: dict[str, float] | None = None
        self.last_jax_trace: dict[str, Any] | None = None
        # Device observatory (ISSUE 19): compile/recompile ledger over
        # every jitted entry point, live HBM accounting, and the
        # always-on transfer audit
        # (TELEMETRY_DEVICE_ENABLE; on by default). The standalone
        # sidecar builds and attaches it in serve() BEFORE warmup so
        # boot compiles land in the ledger; built here, it observes
        # everything from construction on. Failure degrades to "no
        # observatory" — never blocks serving.
        if observatory is None and device_enable:
            try:
                observatory = DeviceObservatory(
                    otel=otel, model=self.model_name, logger=self.logger,
                    ledger_size=device_ledger_size)
            except Exception as e:
                self.logger.warn("device observatory disabled", "error", str(e))
        if observatory is not None and getattr(engine, "observatory", None) is not observatory:
            try:
                observatory.attach(engine)
            except Exception as e:
                self.logger.warn("device observatory attach failed", "error", str(e))
                observatory = None
        self.observatory = observatory
        if self.scheduler.observatory is None:
            self.scheduler.observatory = observatory
        # Streaming fast path (SERVING_EMIT_COALESCE_MS): tokens sampled
        # within this window (seconds; in practice: the same decode step)
        # merge into ONE SSE frame. 0 (the default) keeps the one-frame-
        # per-token OpenAI wire shape byte-identical; the per-token TPOT
        # truth is recorded on the scheduler thread either way.
        self.emit_coalesce = emit_coalesce
        self.router = self._build_router()
        # SERVER_STREAM_COALESCE applies to the sidecar listener too —
        # the documented off-switch must work on BOTH SSE hops.
        self.http = HTTPServer(self.router, logger=self.logger,
                               stream_coalesce=stream_coalesce)
        # OTLP push: decode-loop metrics flow into the gateway's
        # POST /v1/metrics (SURVEY.md §7 stage 7).
        self.metrics_push_url = metrics_push_url
        self.metrics_push_interval = metrics_push_interval
        self._ttft_samples: list[float] = []
        # Token-level streaming samples (ISSUE 3): inter-token latency
        # from the scheduler emit path, queue wait from the per-request
        # phase clock. Appended from the scheduler thread, swapped out
        # whole by the push loop — same GIL-atomic list discipline as
        # _ttft_samples.
        self._tpot_samples: list[float] = []
        self._queue_wait_samples: list[float] = []
        self._pushed_decode_tokens = 0
        # engine.wasted_tokens{reason} as last handed to otel: the
        # scheduler counts, sample_engine_gauges brings the growth over.
        self._wasted_sent: dict[str, int] = {}
        self._push_task: asyncio.Task | None = None

    # ------------------------------------------------------------------
    def _build_router(self) -> Router:
        r = Router()
        r.get("/health", self.health)
        r.get("/v1/models", self.list_models)
        r.post("/v1/chat/completions", self.chat_completions)
        r.get("/props", self.props)
        r.get("/metrics", self.metrics)
        r.get("/debug/timeline", self.debug_timeline)
        r.get("/debug/compile", self.debug_compile)
        r.get("/debug/hbm", self.debug_hbm)
        r.get("/debug/status", self.debug_status)
        r.get("/debug/profile", self.debug_profile)
        r.get("/debug/jax_trace", self.debug_jax_trace)
        if self.admin_enabled:
            r.post("/admin/drain", self.admin_drain)
            r.post("/admin/undrain", self.admin_undrain)
            r.get("/admin/migration", self.admin_migration)
        return r

    async def start(self, host: str = "127.0.0.1", port: int = 8000) -> int:
        if self._own_scheduler:
            self.scheduler.start()
        if self.watchdog is not None:
            self.watchdog.start()
        if self.engine_watchdog is not None:
            self.engine_watchdog.start()
        if self.otel is not None:
            # The degraded gauge must exist from boot: an absent series
            # is indistinguishable from a non-reporting replica, and
            # alerts key on 0 → 1 (code-review finding).
            self.otel.set_engine_degraded(self.model_name, 0)
            # Dispatch verdict from boot too (ISSUE 12 satellite): a
            # silently-degraded gather deployment must be a gauge read,
            # not an XLA-dump archaeology session.
            self.otel.set_attention_path(
                self.model_name, getattr(self.engine, "attention_path", "unknown"))
        bound = await self.http.start(host, port)
        if self.metrics_push_url or (self.tracer.enabled and self.tracer.otlp_endpoint):
            self._push_task = asyncio.create_task(self._metrics_push_loop())
        return bound

    async def shutdown(self) -> None:
        if self._push_task is not None:
            self._push_task.cancel()
        if self.watchdog is not None:
            await self.watchdog.stop()
        if self.engine_watchdog is not None:
            await self.engine_watchdog.stop()
        await self.http.shutdown()
        if self._own_scheduler:
            self.scheduler.stop()
        if self.profiler is not None:
            self.profiler.stop()
        if self.otel is not None:
            # Engine teardown: this replica's saturation gauges describe
            # nothing now — drop the label sets instead of freezing them
            # on /metrics (ISSUE 4 satellite).
            self.otel.remove_engine_gauges(self.model_name)
            self.otel.remove_hbm_gauges(self.model_name)

    def depth_probe(self) -> int:
        """Engine saturation signal for a co-hosted gateway's
        OverloadController.add_depth_probe (ISSUE 2 priority shedding:
        gateway sheds batch work when the engine queue backs up)."""
        return self.scheduler.queue_depth

    # -- planned live migration (ISSUE 11) -----------------------------
    def _migrate_active_streams(self, reason: str) -> int:
        """End every live SSE stream at its current frame boundary with
        no terminal frame and deschedule it, so a continuation-capable
        gateway resumes each one on another replica (byte-identical,
        once-only billing — the PR 9 splice contract). Runs on the event
        loop. Returns how many streams were cut over."""
        if not self.migrate_streams:
            return 0
        n = 0
        for _rid, (gen, q) in list(self._active_streams.items()):
            # Deschedule FIRST: a queued request is dropped before it
            # ever prefills; an admitted one terminates at its next
            # emission and frees its slot + KV pages. The sentinel
            # carries the reason, which rides the published migration
            # record so the gateway attributes the hop from EVIDENCE.
            self.scheduler.cancel(gen)
            q.put_nowait((_MIGRATE, reason))
            n += 1
        if n:
            self.migrated_out += n
            self.logger.info("live streams migrated off this replica",
                             "streams", n, "reason", reason)
        return n

    def begin_drain(self, reason: str = "drain") -> dict[str, Any]:
        """Planned drain (ISSUE 11 tentpole b): flip /health to 503
        "draining" (LBs and the gateway prober route away), refuse new
        generation work with a retryable 503, and migrate live streams
        out. Reversible via ``undrain`` — the engine and scheduler stay
        warm; drain is a routing verdict, not a teardown. A drain
        arriving during a restart window keeps reporting "degraded"
        (both 503) and takes effect when the restart completes."""
        already = self._drain_requested
        self._drain_requested = True
        if self.state == "ok":
            self.state = "draining"
        migrated = 0 if already else self._migrate_active_streams(reason)
        if not already:
            self.logger.info("sidecar draining", "reason", reason,
                             "migrated_streams", migrated)
        return {"state": self.state, "migrated_streams": migrated,
                "already_draining": already}

    def undrain(self) -> dict[str, Any]:
        """Readmit the replica: only a drain is reversible — a degraded
        state (supervised restart in flight) clears itself."""
        if self._drain_requested:
            self._drain_requested = False
            if self.state == "draining":
                self.state = "ok"
            self.logger.info("sidecar undrained; accepting work")
        return {"state": self.state}

    _MIGRATION_RESUME_CAP = 128

    def _record_migration_resume(self, completion_id: str, ids: list[int],
                                 reason: str) -> None:
        """Publish a migrated stream's exact resume ids + the migration
        reason for the gateway to fetch (dict preserves insertion order;
        oldest evicted). The record doubles as the gateway's EVIDENCE
        that this very stream's death was planned — without it, a death
        at a draining/degraded replica is still charged as a failure."""
        self._migration_resume[completion_id] = {"token_ids": list(ids),
                                                 "reason": reason}
        while len(self._migration_resume) > self._MIGRATION_RESUME_CAP:
            del self._migration_resume[next(iter(self._migration_resume))]

    async def admin_drain(self, req: Request) -> Response:
        return Response.json(self.begin_drain())

    async def admin_undrain(self, req: Request) -> Response:
        return Response.json(self.undrain())

    async def admin_migration(self, req: Request) -> Response:
        """GET /admin/migration?id=<completion id> — the authoritative
        resume token ids for a stream this replica migrated out (kept
        until FIFO eviction: the gateway's re-establishment walk may
        retry the fetch)."""
        cid = req.query_get("id")
        rec = self._migration_resume.get(cid)
        if rec is None:
            return Response.json({"error": "unknown migrated stream"}, status=404)
        return Response.json({"id": cid, "token_ids": list(rec["token_ids"]),
                              "reason": rec["reason"]})

    # -- serving-path fault tolerance (ISSUE 7) ------------------------
    def _on_preempt(self, reason: str) -> None:
        """Scheduler-thread hook: KV-pressure preemption telemetry."""
        if self.otel is not None:
            self.otel.record_preemption(self.model_name, reason)

    def _default_engine_factory(self) -> Engine:
        """Rebuild the Engine from its own config — checkpointed engines
        reload from disk, preset engines re-init (same seed → same
        weights), and the fresh instance owns fresh device buffers and a
        fresh page allocator, leaving the wedged one behind."""
        return Engine(self.engine.config)

    async def restart_engine(self, reason: str,
                             forensics: dict[str, Any] | None = None) -> dict[str, Any]:
        """Supervised in-place engine restart (ISSUE 7 tentpole b).

        Health flips degraded (503) for the whole window so failover
        pools route around it. Every queued and in-flight request fails
        with a retryable error (the wedged scheduler thread cannot be
        killed — it is abandoned with its stop flag set). The Engine is
        rebuilt on an executor thread, a fresh Scheduler takes over, and
        health flips back to ready. The process never restarts."""
        self.state = "degraded"
        if self.otel is not None:
            self.otel.set_engine_degraded(self.model_name, 1)
        old_sched = self.scheduler
        info: dict[str, Any] = {"reason": reason,
                                "at": time.time(),  # graftlint: disable=clock-discipline -- epoch forensics stamp
                                "forensics": forensics or {}}
        # Migrate live streams BEFORE aborting the wedged scheduler
        # (ISSUE 11): the migrate sentinel reaches each stream's queue
        # ahead of abort_all's terminal-error token, so the generator
        # ends with no terminal frame and a continuation-capable gateway
        # splices the stream onto another replica — a PR 7 restart
        # becomes invisible to streaming clients, not merely recoverable.
        info["migrated_streams"] = self._migrate_active_streams("restart")
        info["failed_requests"] = old_sched.abort_all()
        self.logger.error("engine wedged; supervised in-place restart", None,
                          "reason", reason,
                          "failed_requests", info["failed_requests"])
        factory = self.engine_factory or self._default_engine_factory

        def _build() -> Engine:
            eng = factory()
            # Re-attach the observatory BEFORE warmup (ISSUE 19): the
            # wrappers are instance attributes, so the replacement engine
            # needs its own set, and warmup() brackets the ledger itself
            # — the rebuilt engine's boot compiles classify as warmup,
            # never as steady-state recompiles.
            if self.observatory is not None:
                self.observatory.attach(eng)
            # Warm before the swap (same contract as serve() at boot):
            # the replacement must not meet its first request cold — a
            # post-restart compile longer than the watchdog deadline
            # would read as another wedge and crash-loop the restart
            # (observed live before this warmup).
            eng.warmup()
            return eng

        loop = asyncio.get_running_loop()
        try:
            new_engine = await loop.run_in_executor(None, _build)
        except Exception as e:
            # The rebuild itself failed (dead driver): stay
            # degraded — health keeps reporting 503 so pools keep
            # routing around — and surface the failed attempt. The
            # watchdog re-trips after another deadline period (natural
            # backoff) and abort_all is idempotent, so the retry costs
            # no duplicate client callbacks.
            info["failed"] = repr(e)
            self.last_restart = info
            self.logger.error("engine rebuild failed; replica stays degraded", e,
                              "reason", reason)
            raise
        sched = Scheduler(new_engine, logger=self.logger,
                          max_queue_depth=old_sched.max_queue_depth,
                          preempt_max=old_sched.preempt_max,
                          preempt_high_water=old_sched.preempt_high_water,
                          clock=self._clock)
        sched.timeline = self.timeline
        sched.observatory = self.observatory
        sched.on_preempt = self._on_preempt
        # Counter continuity: /metrics "preemptions" is cumulative for
        # the PROCESS — a scheduler swap must not make it go backwards
        # (engine_restarts is the signal that a swap happened).
        sched.preemptions = old_sched.preemptions
        sched.wasted = dict(old_sched.wasted)
        sched.start()
        self.engine = new_engine
        self.scheduler = sched
        self._own_scheduler = True
        self.restarts += 1
        self.last_restart = info
        # A drain requested before or during the restart window survives
        # it: the rebuilt replica must stay out of rotation until the
        # operator undrains (code-review finding).
        self.state = "draining" if self._drain_requested else "ok"
        if self.otel is not None:
            self.otel.set_engine_degraded(self.model_name, 0)
            self.otel.record_engine_restart(self.model_name, reason)
            self.otel.set_attention_path(
                self.model_name, getattr(new_engine, "attention_path", "unknown"))
        self.logger.info("engine restart complete", "reason", reason,
                         "restarts", self.restarts)
        return info

    # -- OTLP metrics push ---------------------------------------------
    def record_ttft(self, seconds: float) -> None:
        if len(self._ttft_samples) < _MAX_PENDING_SAMPLES:
            self._ttft_samples.append(seconds)
        if self.otel is not None:
            self.otel.record_server_ttft("tpu-sidecar", "", "tpu", self.model_name, seconds)

    def record_tpot(self, seconds: float) -> None:
        """Inter-token latency off the scheduler emit path."""
        if len(self._tpot_samples) < _MAX_PENDING_SAMPLES:
            self._tpot_samples.append(seconds)
        if self.otel is not None:
            self.otel.record_tpot("tpu-sidecar", "", "tpu", self.model_name, seconds)

    def record_queue_wait(self, seconds: float) -> None:
        if len(self._queue_wait_samples) < _MAX_PENDING_SAMPLES:
            self._queue_wait_samples.append(seconds)
        if self.otel is not None:
            self.otel.record_queue_wait("tpu-sidecar", "", "tpu", self.model_name, seconds)

    def sample_engine_gauges(self) -> dict[str, float]:
        """Engine/Scheduler saturation gauges (ISSUE 3): slot occupancy,
        KV page utilization, queue depth, speculative acceptance. Sampled
        on request completion and on every /metrics scrape; mirrored into
        a co-hosted OpenTelemetry Registry when one is wired."""
        sched = self.scheduler
        gauges: dict[str, float] = {
            "slot_occupancy": sched.active_requests() / max(1, self.engine.config.max_slots),
            "kv_page_utilization": self.engine.kv_utilization(),
            "queue_depth": float(sched.queue_depth),
        }
        spec_rate = None
        if (self.engine.spec or getattr(self.engine, "mtp", False)) and sched.spec_slot_rounds:
            # (a model that drafts for itself feeds these from its step programs: ISSUE 56)
            spec_rate = sched.spec_emitted / sched.spec_slot_rounds
            gauges["spec_tokens_per_slot_round"] = spec_rate
        if self.otel is not None:
            self.otel.set_engine_gauges(
                self.model_name,
                slot_occupancy=gauges["slot_occupancy"],
                kv_utilization=gauges["kv_page_utilization"],
                queue_depth=sched.queue_depth,
                spec_tokens_per_slot_round=spec_rate,
            )
            for reason, total in sched.wasted.items():
                grown = total - self._wasted_sent.get(reason, 0)
                if grown > 0:
                    self._wasted_sent[reason] = total
                    self.otel.record_wasted_tokens(self.model_name, reason, grown)
        if self.observatory is not None:
            # engine.hbm.{plan,live,peak}_bytes ride the same cadence
            # (ISSUE 19); off-TPU only the plan gauge exists — absent
            # live/peak series are the honest "not measured".
            self.observatory.sample_hbm_gauges()
        return gauges

    @staticmethod
    def _delta_histogram(name: str, samples: list[float], bounds: list[float],
                         attrs: list[dict[str, Any]]) -> dict[str, Any]:
        counts = [0] * (len(bounds) + 1)
        for s in samples:
            i = 0
            while i < len(bounds) and s > bounds[i]:
                i += 1
            counts[i] += 1
        return {
            "name": name,
            "histogram": {
                "aggregationTemporality": 1,
                "dataPoints": [{
                    "bucketCounts": [str(c) for c in counts],
                    "explicitBounds": bounds,
                    "sum": sum(samples),
                    "count": str(len(samples)),
                    "attributes": attrs,
                }],
            },
        }

    def _otlp_payload(self) -> dict[str, Any] | None:
        """Delta OTLP-JSON payload of the TTFT, inter-token-latency, and
        queue-wait histograms accumulated since the last push."""
        batches = [
            ("gen_ai.server.time_to_first_token", self._ttft_samples, _PUSH_TTFT_BOUNDS),
            ("gen_ai.server.time_per_output_token", self._tpot_samples, _PUSH_TPOT_BOUNDS),
            ("gen_ai.server.time_in_queue", self._queue_wait_samples, _PUSH_TTFT_BOUNDS),
        ]
        self._ttft_samples, self._tpot_samples, self._queue_wait_samples = [], [], []
        attrs = [
            {"key": "gen_ai.provider.name", "value": {"stringValue": "tpu"}},
            {"key": "gen_ai.request.model", "value": {"stringValue": self.model_name}},
        ]
        metrics = [self._delta_histogram(name, samples, bounds, attrs)
                   for name, samples, bounds in batches if samples]
        if self.observatory is not None:
            # HBM accounting rides the push too (ISSUE 19): the gateway
            # ingest maps engine.hbm.* onto last-value gauges. live/peak
            # appear only when the backend actually measured them.
            hbm = self.observatory.hbm_snapshot()
            points = [("engine.hbm.plan_bytes",
                       (hbm.get("plan") or {}).get("plan_bytes"))]
            if hbm.get("measured"):
                points.append(("engine.hbm.live_bytes", hbm.get("live_bytes")))
                points.append(("engine.hbm.peak_bytes", hbm.get("peak_bytes")))
            for name, val in points:
                if val:
                    metrics.append({
                        "name": name,
                        "gauge": {"dataPoints": [{"asDouble": float(val),
                                                  "attributes": attrs}]},
                    })
        if not metrics:
            return None
        return {
            "resourceMetrics": [{
                "resource": {"attributes": [
                    {"key": "service.name", "value": {"stringValue": "tpu-sidecar"}}]},
                "scopeMetrics": [{"metrics": metrics}],
            }]
        }

    async def _metrics_push_loop(self) -> None:
        from inference_gateway_tpu.netio.client import HTTPClient

        client = HTTPClient()
        while True:
            await asyncio.sleep(self.metrics_push_interval)
            # Drain pending samples on every cycle even when only trace
            # export is configured — the cap above bounds the no-loop
            # case, this keeps the looped case at steady state.
            payload = self._otlp_payload()
            if payload is not None and self.metrics_push_url:
                try:
                    await client.post(
                        self.metrics_push_url,
                        json.dumps(payload).encode(),
                        headers={"Content-Type": "application/json", "X-Source": "tpu-sidecar"},
                    )
                except Exception as e:
                    self.logger.warn("metrics push failed", "error", str(e))
            # Standalone-process tracing (ISSUE 3): the phase spans built
            # in _finalize_request export OTLP/JSON on the same cadence.
            if self.tracer.enabled and self.tracer.otlp_endpoint:
                await self.tracer.export_once(client)

    # -- handlers ------------------------------------------------------
    HEALTH_STALL_SECONDS = 60.0

    def _load_report(self) -> dict[str, Any]:
        """The /health load fields (ISSUE 11 satellite): queue depth, KV
        page utilization, and slot occupancy ride the body the gateway's
        ``HealthProber`` already fetches — it doubles as the fleet load
        reporter with no second probe endpoint. Foreign (non-TPU)
        deployments keep their status-only contract; the prober parses
        these fields only when present."""
        return {
            "queue_depth": self.scheduler.queue_depth,
            "kv_page_utilization": round(self.engine.kv_utilization(), 4),
            "active_slots": self.scheduler.active_requests(),
            "max_slots": self.engine.config.max_slots,
        }

    async def health(self, req: Request) -> Response:
        """Liveness + device-stall detection: active requests with no
        completed engine step for HEALTH_STALL_SECONDS means the
        accelerator is wedged — report degraded with 503
        so orchestrators can recycle the replica. During a supervised
        engine restart (ISSUE 7) the same 503 "degraded" flows, and a
        planned drain (ISSUE 11) reports 503 "draining", so failover
        pools route around both windows without external help. Every
        body carries the load report (ISSUE 11 satellite)."""
        load = self._load_report()
        if self.state == "draining":
            return Response.json({
                "status": "draining",
                "reason": "planned drain in progress; streams migrated",
                **load,
            }, status=503)
        if self.state == "degraded":
            return Response.json({
                "status": "degraded",
                "reason": "supervised engine restart in progress",
                "restarts": self.restarts,
                **load,
            }, status=503)
        stalled = (
            self.scheduler.active_requests() > 0
            and self._clock.now() - self.scheduler.last_step_time > self.HEALTH_STALL_SECONDS
        )
        if stalled:
            return Response.json({
                "status": "degraded",
                "reason": "no engine step completed recently with active requests",
                "seconds_since_last_step": round(self._clock.now() - self.scheduler.last_step_time, 1),
                **load,
            }, status=503)
        return Response.json({"status": "ok", **load})

    async def list_models(self, req: Request) -> Response:
        return Response.json({
            "object": "list",
            "data": [{
                "id": self.model_name,
                "object": "model",
                "created": self.created,
                "owned_by": "tpu",
                "served_by": "tpu",
                "context_window": self.engine.context_window(),
            }],
        })

    async def props(self, req: Request) -> Response:
        """llama.cpp-compatible /props (context_window.go:86-100)."""
        return Response.json({
            "default_generation_settings": {"n_ctx": self.engine.context_window()},
            "model": self.model_name,
            "total_slots": self.engine.config.max_slots,
        })

    def _metrics_snapshot(self) -> dict:
        m = dict(self.engine.metrics)
        m["queue_depth"] = self.scheduler.queue_depth
        m["active_requests"] = self.scheduler.active_requests()
        if self.engine.spec or getattr(self.engine, "mtp", False):
            # Mean tokens per draft+verify round per slot = 1 + mean
            # accepted draft tokens (the speculative speedup upper bound).
            m["spec_rounds"] = self.scheduler.spec_rounds
            m["spec_emitted_tokens"] = self.scheduler.spec_emitted
            m["spec_accepted"] = getattr(self.scheduler, "spec_accepted", 0)
            if self.scheduler.spec_slot_rounds:
                m["spec_tokens_per_slot_round"] = round(
                    self.scheduler.spec_emitted / self.scheduler.spec_slot_rounds, 3)
        m["uptime_seconds"] = round(self._clock.now() - self._started, 3)
        m["preemptions"] = self.scheduler.preemptions
        m["engine_restarts"] = self.restarts
        m["streams_migrated_out"] = self.migrated_out
        gauges = self.sample_engine_gauges()  # refresh on every scrape
        m["slot_occupancy"] = round(gauges["slot_occupancy"], 4)
        m["kv_page_utilization"] = round(gauges["kv_page_utilization"], 4)
        if self.engine.allocator is not None:
            m["kv_pages_total"] = self.engine.allocator.num_pages
            m["kv_pages_free"] = self.engine.allocator.free_page_count()
        if self.engine.prefix_cache is not None:
            m["prefix_cache"] = self.engine.prefix_cache.stats()
        if self.engine.structured is not None:
            m["structured"] = self.engine.structured.stats()
        m["wasted_tokens"] = sum(self.scheduler.wasted.values())
        if self.observatory is not None:
            # Device observatory flat numerics (ISSUE 19): the transfer
            # table plus the chained-submit invariant as its own scalar
            # — engine.transfers{direction="h2d",path="chain"} staying 0
            # on a live scrape is the production proof of the host-free
            # decode chain.
            m["compiles"] = self.observatory.ledger.compiles
            m["recompiles"] = self.observatory.ledger.recompile_count()
            m["transfers"] = self.observatory.transfers.snapshot()
            m["h2d_chain_transfers"] = self.observatory.transfers.count("h2d", "chain")
            hbm = self.observatory.hbm_snapshot()
            plan_bytes = (hbm.get("plan") or {}).get("plan_bytes")
            if plan_bytes:
                m["hbm_plan_bytes"] = plan_bytes
            if hbm.get("measured"):
                m["hbm_live_bytes"] = hbm.get("live_bytes")
                m["hbm_peak_bytes"] = hbm.get("peak_bytes")
        return m

    async def metrics(self, req: Request) -> Response:
        """GET /metrics — JSON by default; Prometheus text format when
        the client asks for it (Accept: text/plain or ?format=prometheus)
        so the monitoring example's Prometheus can scrape the sidecar
        directly (tpu_sidecar_* series on the Grafana dashboard)."""
        m = self._metrics_snapshot()
        accept = req.headers.get("Accept") or ""
        if "text/plain" not in accept and req.query_get("format") != "prometheus":
            return Response.json(m)
        flat = dict(m)
        prefix_stats = flat.pop("prefix_cache", None)
        if isinstance(prefix_stats, dict):
            for k, v in prefix_stats.items():
                flat[f"prefix_cache_{k}"] = v
        structured_stats = flat.pop("structured", None)
        if isinstance(structured_stats, dict):
            for k, v in structured_stats.items():
                flat[f"structured_{k}"] = v
        transfers = flat.pop("transfers", None)
        if isinstance(transfers, dict):
            # h2d/chain -> tpu_sidecar_transfers_h2d_chain (ISSUE 19):
            # the invariant series must be scrapeable in text format too.
            for key, slot in transfers.items():
                flat[f"transfers_{key.replace('/', '_')}"] = slot["count"]
        lines = []
        for key, val in sorted(flat.items()):
            if not isinstance(val, (int, float)):
                continue
            name = f"tpu_sidecar_{key}"
            kind = "counter" if key.endswith(("_tokens", "_steps", "_batches", "hits", "misses")) else "gauge"
            lines.append(f"# TYPE {name} {kind}")
            lines.append(f"{name} {val}")
        return Response.text("\n".join(lines) + "\n", content_type="text/plain; version=0.0.4")

    # -- performance introspection (ISSUE 4) ---------------------------
    async def debug_timeline(self, req: Request) -> Response:
        """GET /debug/timeline — the engine decode-step ring: per-step
        wall time, kind, batch occupancy, tokens, KV utilization.
        ``?n=`` bounds the tail returned."""
        if self.timeline is None:
            return Response.json(
                {"error": "timeline disabled (TELEMETRY_PROFILING_TIMELINE_SIZE=0)"},
                status=404)
        try:
            n = int(req.query_get("n", "0") or 0)
        except ValueError:
            return Response.json({"error": "n must be an integer"}, status=400)
        stats = self.timeline.stats()
        return Response.json({
            "model": self.model_name,
            "steps": stats["steps"],
            "records": stats["records"],
            "entries": self.timeline.tail(n if n > 0 else None),
        })

    async def debug_compile(self, req: Request) -> Response:
        """GET /debug/compile — the device compile/recompile ledger
        (ISSUE 19): every XLA compilation of a jitted engine entry point
        with program name, static shape signature and compile wall-ms —
        plus the steady-state recompile events with the per-argument
        signature diff that triggered each one. A nonzero ``recompiles`` after
        warmup is a shape-stability bug, not noise."""
        if self.observatory is None:
            return Response.json(
                {"error": "device observatory disabled (TELEMETRY_DEVICE_ENABLE)"},
                status=404)
        snap = self.observatory.ledger.snapshot()
        snap["model"] = self.model_name
        return Response.json(snap)

    async def debug_hbm(self, req: Request) -> Response:
        """GET /debug/hbm — live device memory against the analytic plan
        (ISSUE 19): runtime ``memory_stats()`` when the backend exposes
        it, framed ``measured: false`` otherwise (host numbers are never
        presented as device truth); the weights + KV-pool byte plan; and
        the KV page pool's high-water mark."""
        if self.observatory is None:
            return Response.json(
                {"error": "device observatory disabled (TELEMETRY_DEVICE_ENABLE)"},
                status=404)
        snap = self.observatory.hbm_snapshot()
        snap["model"] = self.model_name
        return Response.json(snap)

    async def debug_status(self, req: Request) -> Response:
        """GET /debug/status — one JSON snapshot of the sidecar's
        introspection state: engine occupancy, timeline summary, the
        slow-request log, and profiler/watchdog health. ``?brief=1``
        answers with just the bounded operator subset the gateway's
        health prober caches for /debug/fleet (ISSUE 18) — cheap enough
        to ride every probe round."""
        if req.query_get("brief"):
            brief = {
                "model": self.model_name,
                "uptime_seconds": round(self._clock.now() - self._started, 3),
                "active_requests": self.scheduler.active_requests(),
                "queue_depth": self.scheduler.queue_depth,
                "state": self.state,
                "preemptions": self.scheduler.preemptions,
                "engine_restarts": self.restarts,
                "streams_migrated_out": self.migrated_out,
            }
            if self.observatory is not None:
                # Bounded device summary (ISSUE 19) — rides every fleet
                # probe round, so compact by construction.
                brief["device"] = self.observatory.fleet_summary()
            return Response.json(brief)
        family_status = getattr(self.engine, "family_status", None)  # test doubles have none
        status: dict[str, Any] = {
            "model": self.model_name,
            "uptime_seconds": round(self._clock.now() - self._started, 3),
            "active_requests": self.scheduler.active_requests(),
            "queue_depth": self.scheduler.queue_depth,
            "state": self.state,
            "preemptions": self.scheduler.preemptions,
            "engine_restarts": self.restarts,
            "streams_migrated_out": self.migrated_out,
            # The device this process holds, as JAX reports it — never
            # assumed by a reader (chip_smoke.py checks it from here).
            "accelerator": getattr(self.engine, "device", None),
            # The engine's mesh as axis -> size (ISSUE 28): {"tp": 1} on one
            # device. A reader that counts a chip's bytes wants to know
            # how the weights are split, not assume it.
            "mesh": getattr(self.engine, "mesh_axes", None),
            # The paged-attention dispatch verdict (ISSUE 12 satellite):
            # which path this engine's layouts take and why — "gather"
            # here means the ~10.6×-slower fallback is live.
            "attention_path": {
                "path": getattr(self.engine, "attention_path", "unknown"),
                "reason": getattr(self.engine, "attention_path_reason", ""),
                "mixed_step": getattr(self.engine, "mixed_ok", False),
            },
            # The sampler's verdict (ISSUE 31): the candidate selection
            # this engine's (vocabulary, top_k) takes — "grouped" sorts
            # `candidates` of the vocabulary's columns, "direct" all of them.
            "sampler": getattr(self.engine, "sampler_plan", None),
            # A mixed step's attention (ISSUE 34, ISSUE 44): a row of two
            # queries or more is walked `wide_tile` queries at a time
            # (`rows_per_pass` rows a KV head's MXU pass), up to
            # `wide_pages_per_visit` pages a visit; the one-query rows of
            # the packed prefix take `one_query_rows` (the decode kernel,
            # `one_query_pages_per_visit` pages a visit); None where no
            # mixed step runs. The timeline's `mixed` records count the
            # tokens of the rows that took the wide tile (`attn_wide_tokens`)
            # and the pages its tiles walked and masked
            # (`attn_pages_walked`, `attn_pages_masked`).
            "ragged_attention": getattr(self.engine, "ragged_attention_plan", None),
            # The decode attention kernel's page walk (ISSUE 37): up to
            # `pages_per_visit` pages of a slot are fetched, scored and
            # weighed as one tile, operands in `operand_dtype`, and an
            # empty slot costs no visit (`skips_empty_slots`; how often it
            # engages is `batch` of the timeline's `decode` records against
            # the slots). None where no decode program holds the kernel.
            "decode_attention": getattr(self.engine, "decode_attention_plan", None),
            # The pools' row writes (ISSUE 62): for each step program whose
            # family writes through ``ops/pool_write.write_rows`` the `path` its
            # `rows` of `row_bytes` take a layer ("runs": a copy a tile of
            # `tile_rows` rows that a run covers; "scatter": XLA's, a row at a
            # time) and the `reason`. The timeline's `mixed` records count the
            # copies a pool takes (`write_copies`). None where the model writes
            # its pool itself.
            "pool_write": getattr(self.engine, "pool_write_plan", None),
            # The model family's blocks, written by its model module (``Family.status``,
            # models/family.py; docs/observability.md lists them). A state store (ISSUE 32,
            # 43, 50, 54): ``state_store``, ``cache_layers``, ``state_resets``,
            # ``prefix_hits_declined``, and ``ssd`` or ``kda``; an indexer (ISSUE 36): ``dsa``;
            # a latent attention (ISSUE 47): ``latent_pool``; a next-token module (ISSUE 56):
            # ``mtp`` and ``vocab``; a mixture (ISSUE 41): ``router``, ``shared_experts``,
            # ``dense_prefix``, and with windowed layers ``cache_layers`` {window, full}. The
            # `decode` and `mixed` timeline records carry the counts each names there.
            **(family_status(self.scheduler) if family_status else {}),
            # Desynchronized decode (ISSUE 14): the shape at which the
            # decode loop stops on device and chains host-free.
            "decode": {
                "chunk": self.engine.config.decode_chunk,
                "pipeline_depth": self.engine.config.pipeline_depth,
            },
            # Delivery after dispatch (ISSUE 29): of the step-boundary
            # flushes, how many waited for the next dispatch (an
            # admission's decode rows) and how many ran in place. One step
            # ahead of the host (ISSUE 40): of the mixed steps and fresh
            # decode chunks, how many read a row's token from an unfetched
            # step's block on the device, and how many took every token
            # from the host. ``chunks_in_flight_max`` (ISSUE 48): the most
            # decode chunks ever dispatched and unfetched at once; an
            # admission's mixed record says what it drained
            # (``prep.drained_steps``).
            "scheduler": {
                "steps_completed": self.scheduler.steps_completed,
                "flushes_deferred": self.scheduler.flushes_deferred,
                "flushes_immediate": self.scheduler.flushes_immediate,
                "pipelined_submits": self.scheduler.pipelined_submits,
                "host_stepped_submits": self.scheduler.host_stepped_submits,
                "chunks_in_flight_max": self.scheduler.chunks_in_flight_max,
            },
        }
        if self.engine.structured is not None:
            # Structured-outputs snapshot (ISSUE 13): mask-cache hit
            # rates, device-table occupancy, live constrained slots.
            status["structured"] = self.engine.structured.stats()
        if self.last_restart is not None:
            status["last_restart"] = self.last_restart
        if self.engine_watchdog is not None:
            status["engine_watchdog"] = self.engine_watchdog.stats()
        if self.timeline is not None:
            status["timeline"] = self.timeline.stats()
        if self.startup is not None:
            # Where the start went (ISSUE 24), seconds by stage; what the
            # stages leave of total_s is config, tokenizer and mesh set-up.
            status["startup"] = self.startup
        if self.last_jax_trace is not None:
            # The last /debug/jax_trace capture on the span clock, so a
            # reader can lay timeline records over the device trace.
            status["jax_trace"] = self.last_jax_trace
        # Work the engine did that no client saw, by reason: what the
        # scheduler counted, as engine.wasted_tokens{reason} has it.
        status["wasted_tokens"] = dict(self.scheduler.wasted)
        if self.observatory is not None:
            # The full device pane (ISSUE 19): compile ledger, transfer
            # audit, HBM accounting — one stop for "what has the device
            # actually been doing".
            status["device"] = self.observatory.snapshot()
        if self.slow_log is not None:
            status["slow_requests"] = self.slow_log.snapshot()
        if self.profiler is not None:
            status["profiling"] = self.profiler.stats()
        if self.watchdog is not None:
            status["eventloop"] = self.watchdog.stats()
        return Response.json(status)

    async def debug_profile(self, req: Request) -> Response:
        """GET /debug/profile?seconds=N&hz=M — on-demand collapsed-stack
        capture (``?mode=continuous`` reads the ring instead)."""
        status, ctype, body = await handle_profile_query(
            self.profiler, seconds=req.query_get("seconds"),
            hz=req.query_get("hz"), mode=req.query_get("mode"))
        return Response.text(body, status=status, content_type=ctype)

    async def debug_jax_trace(self, req: Request) -> Response:
        """GET /debug/jax_trace?seconds=N&dir=PATH&python_tracer=0|1 —
        guarded ``jax.profiler.trace`` device capture; a no-op (with the
        reason) off-TPU. ``python_tracer=0`` leaves the profiler's Python
        tracer off: the scheduler thread's annotations still say what the
        host was doing, without a frame event per call. The reply (and
        /debug/status ``jax_trace``) places the capture on the span clock
        the timeline's ``start_ts`` / ``ts`` are on."""
        try:
            seconds = float(req.query_get("seconds", "2") or 2.0)
        except ValueError:
            return Response.json({"error": "seconds must be a number"}, status=400)
        log_dir = req.query_get("dir", "/tmp/jax-trace")
        python_tracer = req.query_get("python_tracer", "1") != "0"
        result = await asyncio.get_running_loop().run_in_executor(
            None, jax_trace_capture, log_dir, seconds, python_tracer)
        if result.get("captured"):
            self.last_jax_trace = {k: result[k] for k in (
                "start_epoch_ns", "stop_epoch_ns", "capture_epoch_ns", "python_tracer")}
        return Response.json(result, status=200 if result.get("captured") else 409)

    # ------------------------------------------------------------------
    def _decode_images(self, messages: list[dict[str, Any]]) -> list:
        """Pull image_url parts (data: URLs) into vision-ready arrays."""
        import base64
        import io

        import numpy as np

        cfg = self.engine.vision_cfg
        images = []
        for m in messages:
            content = m.get("content")
            if not isinstance(content, list):
                continue
            for part in content:
                if not (isinstance(part, dict) and part.get("type") == "image_url"):
                    continue
                url = (part.get("image_url") or {}).get("url", "")
                if not url.startswith("data:"):
                    continue  # zero-egress: only inline images
                try:
                    from PIL import Image

                    b64 = url.split(",", 1)[1]
                    img = Image.open(io.BytesIO(base64.b64decode(b64))).convert("RGB")
                    img = img.resize((cfg.image_size, cfg.image_size))
                    arr = np.asarray(img, np.float32) / 127.5 - 1.0  # CLIP-style [-1, 1]
                    images.append(arr)
                except Exception:
                    self.logger.warn("failed to decode inline image")
        return images

    def _prepare(self, body: dict[str, Any]) -> tuple[GenRequest, dict[str, Any]]:
        messages = body.get("messages") or []
        prompt_ids = self.engine.tokenizer.apply_chat_template(messages)
        embeds = None
        refused = getattr(self.engine, "family", None) and self.engine.family.refuses.get("image_part")
        if refused and any(
                isinstance(part, dict) and part.get("type") == "image_url"
                for m in messages if isinstance(m.get("content"), list) for part in m["content"]):
            raise _BadRequest(f"{self.model_name}: {refused}", code="unsupported_content", param="messages")
        if self.engine.vision_cfg is not None:
            images = self._decode_images(messages)
            if images:
                prompt_ids, embeds = self.engine.prepare_multimodal(prompt_ids, images)
        # Continuation extension (ISSUE 9): the request re-enters with
        # prompt + generated-so-far as the prefill prompt — the SAME
        # resume path KV-pressure preemption uses (PrefixCache makes the
        # re-prefill cheap) — so the first sampled token is the next NEW
        # token and ``resume_generated`` spans max_tokens across the
        # whole logical stream and bills continuation tokens exactly
        # once. The original completion id/created are echoed in the
        # chunk envelope so the gateway splice stays byte-identical.
        cont = body.get("continuation")
        resume_ids: list[int] = []
        cont_id: str = ""
        cont_created: int | None = None
        if isinstance(cont, dict):
            ids = cont.get("token_ids")
            if ids is not None:
                resume_ids = [int(t) for t in ids]
            elif cont.get("text"):
                resume_ids = self.engine.tokenizer.encode(cont["text"], add_bos=False)
            cont_id = str(cont.get("id") or "")
            created = cont.get("created")
            cont_created = int(created) if isinstance(created, (int, float)) else None
        max_tokens = body.get("max_completion_tokens") or body.get("max_tokens") or 256
        stop = body.get("stop")
        stop_strings: list[str] = [stop] if isinstance(stop, str) else list(stop or [])
        seed = body.get("seed")
        grammar = self._prepare_grammar(body, resume_ids)
        req = GenRequest(
            prompt_ids=prompt_ids + resume_ids,
            max_tokens=int(max_tokens),
            temperature=float(body.get("temperature") or 0.0),
            top_p=float(body.get("top_p") or 1.0),
            embeds=embeds,
            seed=int(seed) if seed is not None else None,
            resume_generated=len(resume_ids),
            grammar=grammar,
            logit_bias=self._prepare_logit_bias(body),
        )
        meta = {
            "id": cont_id or "chatcmpl-" + uuid.uuid4().hex[:24],
            "created": cont_created if cont_created is not None else int(time.time()),  # graftlint: disable=clock-discipline -- epoch wire format
            "model": body.get("model") or self.model_name,
            # The ORIGINAL prompt: resume tokens are completion tokens
            # (already billed by the replica that generated them), not
            # input — usage and the wide event keep the unkilled shape.
            "prompt_tokens": len(prompt_ids),
            "resume_ids": resume_ids,
            "resume_tokens": len(resume_ids),
            "stop_strings": stop_strings,
        }
        return req, meta

    def _prepare_grammar(self, body: dict[str, Any], resume_ids: list[int]):
        """Compile ``response_format`` into a per-request GrammarSession
        (ISSUE 13), fast-forwarded through any continuation resume ids so
        a spliced constrained stream is byte-identical to an unkilled
        one. Raises _BadRequest (400 ``unsupported_schema``) for formats
        the compiler cannot lower — BEFORE any slot/page allocation."""
        from inference_gateway_tpu.structured.compiler import UnsupportedSchemaError

        response_format = body.get("response_format")
        if response_format is None or (
                isinstance(response_format, dict)
                and response_format.get("type") in (None, "text")):
            return None
        runtime = self.engine.structured
        if runtime is None:
            raise _BadRequest(
                "structured outputs are disabled on this engine "
                "(STRUCTURED_ENABLE)", code="unsupported_schema",
                param="response_format")
        try:
            session = runtime.session_for(response_format)
        except UnsupportedSchemaError as e:
            raise _BadRequest(str(e), code="unsupported_schema",
                              param="response_format",
                              extra={"reason": e.reason}) from e
        compile_s, cache_hit = runtime.last_compile
        if self.otel is not None:
            self.otel.record_schema_compile(self.model_name, compile_s, cache_hit)
        if session is not None and resume_ids:
            if not session.fast_forward(resume_ids):
                raise _BadRequest(
                    "continuation resume tokens are not a valid prefix of "
                    "the requested response_format grammar",
                    code="invalid_continuation", param="continuation")
        return session

    def _prepare_logit_bias(self, body: dict[str, Any]) -> dict[int, float] | None:
        """Parse/validate OpenAI ``logit_bias`` (ISSUE 13 satellite):
        token ids must exist in the model vocabulary (400 otherwise),
        biases clamp to the OpenAI [-100, 100] range."""
        raw = body.get("logit_bias")
        if not raw:
            return None
        if not isinstance(raw, dict):
            raise _BadRequest("logit_bias must be an object",
                              code="invalid_logit_bias", param="logit_bias")
        if self.engine.structured is None:
            raise _BadRequest(
                "logit_bias requires the structured-outputs subsystem "
                "(STRUCTURED_ENABLE)", code="invalid_logit_bias",
                param="logit_bias")
        vocab = self.engine.model_cfg.vocab_size
        out: dict[int, float] = {}
        for key, value in raw.items():
            try:
                token_id = int(key)
                bias = float(value)
            except (TypeError, ValueError):
                raise _BadRequest(
                    f"logit_bias entry {key!r} is not a token-id/number pair",
                    code="invalid_logit_bias", param="logit_bias") from None
            if not 0 <= token_id < vocab:
                raise _BadRequest(
                    f"logit_bias token id {token_id} is outside the model "
                    f"vocabulary (0..{vocab - 1})",
                    code="invalid_logit_bias", param="logit_bias",
                    extra={"vocab_size": vocab})
            out[token_id] = max(-100.0, min(100.0, bias))
        return out

    async def chat_completions(self, req: Request) -> Response:
        # The phase clock's first stamp (ISSUE 24): before the body is
        # parsed, templated and tokenised.
        recv_ns = time.time_ns()
        try:
            body = req.json()
        except (ValueError, UnicodeDecodeError):
            return Response.json({"error": "invalid JSON body"}, status=400)
        if not body.get("messages"):
            return Response.json({"error": "messages is required"}, status=400)

        try:
            # Request preparation runs OFF the event loop: chat-template
            # tokenization is CPU work, and a cold response_format
            # compile (schema -> byte DFA -> full-vocab token automaton;
            # up to ~1s on large vocabularies) would otherwise stall
            # every concurrent stream and /health for its whole duration
            # (review finding). The compiler cache is thread-safe.
            gen, meta = await asyncio.get_running_loop().run_in_executor(
                None, self._prepare, body)
        except _BadRequest as bad:
            return Response.json(bad.payload, status=400)
        gen.phase_ns["recv"] = recv_ns
        if len(gen.prompt_ids) >= self.engine.context_window():
            return Response.json({"error": "prompt exceeds context window"}, status=400)
        # Oversized-prompt fast-fail (ISSUE 7 satellite): in modes with
        # no long-prompt prefill path (paged/MoE/spec/multimodal), a
        # prompt above the largest prefill bucket can only ever fail at
        # admission — reject it with a structured 400 BEFORE a slot or
        # any KV pages are allocated, instead of streaming a
        # finish_reason "error".
        limit = self.engine.max_prompt_len(multimodal=gen.embeds is not None)
        if len(gen.prompt_ids) > limit:
            return Response.json({"error": {
                "message": (f"prompt of {len(gen.prompt_ids)} tokens exceeds the "
                            f"largest admittable prompt ({limit} tokens) for this "
                            "engine configuration"),
                "type": "invalid_request_error",
                "param": "messages",
                "code": "prompt_too_long",
                "prompt_tokens": len(gen.prompt_ids),
                "max_prompt_tokens": limit,
            }}, status=400)
        stream = bool(body.get("stream"))
        include_usage = bool((body.get("stream_options") or {}).get("include_usage"))

        loop = asyncio.get_running_loop()
        q: asyncio.Queue = asyncio.Queue()
        arrival = self._clock.now()
        first_token_seen = False
        last_token_t: list[float | None] = [None]
        traceparent = req.headers.get("traceparent")
        pending: list[tuple[int, float, bool, str | None]] = []

        def cb(token: int, logprob: float, finished: bool, reason: str | None) -> None:
            # Runs on the scheduler thread — this IS the emit path, so
            # the inter-token gaps recorded here are true per-token
            # latency, not relay-block arrival jitter (ISSUE 3). Tokens
            # buffer locally; flush() hands the step's whole batch to the
            # event loop in ONE call_soon_threadsafe (one loop-wakeup
            # syscall per decode step, not per token).
            nonlocal first_token_seen
            now = self._clock.now()
            if not first_token_seen:
                first_token_seen = True
                self.record_ttft(now - arrival)
            elif last_token_t[0] is not None:
                self.record_tpot(now - last_token_t[0])
            last_token_t[0] = now
            pending.append((token, logprob, finished, reason))

        def flush() -> None:
            # Scheduler thread, step boundary. copy+clear under the GIL.
            if pending:
                batch = pending.copy()
                pending.clear()
                loop.call_soon_threadsafe(q.put_nowait, batch)

        gen.callback = cb
        gen.flush_callback = flush
        want_logprobs = bool(body.get("logprobs"))

        # Bounded admission: a full scheduler queue sheds with 429 +
        # Retry-After derived from observed service time and backlog —
        # BEFORE any SSE headers go out (ISSUE 2). A stopped scheduler
        # (supervised engine restart in flight, ISSUE 7) is a retryable
        # 503 — submitting there would hang the client forever.
        if self.state == "draining":
            # Planned drain (ISSUE 11): this replica is leaving the pool
            # — a retryable 503 sends the gateway's establishment walk to
            # the next candidate before any SSE headers go out.
            resp = Response.json({"error": {
                "message": "sidecar is draining; retry another replica",
                "type": "server_error",
                "code": "draining",
            }}, status=503)
            resp.headers.set("Retry-After", "1")
            return resp
        try:
            if self.state == "degraded":
                raise SchedulerStoppedError("engine restart in progress")
            self.scheduler.submit(gen)
        except SchedulerStoppedError:
            resp = Response.json({"error": {
                "message": "engine restart in progress; retry",
                "type": "server_error",
                "code": "engine_restarting",
            }}, status=503)
            resp.headers.set("Retry-After", "1")
            return resp
        except SchedulerSaturatedError:
            resp = Response.json(
                {"error": "Engine is saturated. Please retry later."}, status=429)
            resp.headers.set("Retry-After", str(self._retry_after_hint()))
            return resp

        if stream:
            # Live-stream registry (ISSUE 11): drain/restart inject the
            # migrate sentinel through this map; the generator's finally
            # removes the entry on every exit path.
            self._active_streams[gen.request_id] = (gen, q)
            return StreamingResponse.sse(
                self._stream_chunks(gen, meta, q, include_usage, arrival, traceparent))

        # Non-streaming: drain the queue (one item per decode step, each
        # a batch of tokens) to completion.
        detok = self._seed_detok(meta)
        seed_len = len(detok.emitted)
        completion_tokens = 0
        reason = "stop"
        done = False
        logprob_content: list[dict[str, Any]] = []
        while not done:
            for token, logprob, finished, fin_reason in await q.get():
                if not (finished and fin_reason == "stop"):
                    delta = detok.push(self.engine.tokenizer, token)
                    if want_logprobs:
                        logprob_content.append({"token": delta, "logprob": logprob})
                completion_tokens += 1
                if finished:
                    reason = fin_reason or "stop"
                    done = True
                    break
        self._observe_service(self._clock.now() - arrival)
        self._finalize_request(gen, meta, traceparent, completion_tokens, stream=False,
                               finish_reason=reason)
        if reason == "error":
            # Engine-side failure (device error, restart, admission
            # fault) on a request that streamed nothing to the client:
            # surface it as a RETRYABLE 503 + Retry-After (ISSUE 7), not
            # a well-formed completion with finish_reason "error" — the
            # gateway's resilience layer retries/fails over 503s.
            resp = Response.json({"error": {
                "message": "generation failed on the serving engine; retry",
                "type": "server_error",
                "code": "engine_failure",
            }}, status=503)
            resp.headers.set("Retry-After", str(self._retry_after_hint()))
            return resp
        text, reason = self._apply_stop_strings(detok.emitted, meta["stop_strings"], reason)
        # A continuation returns only the NEW tail (the caller already
        # holds the resume prefix); usage reports the whole logical
        # stream so the client-visible totals match an unkilled run.
        text = text[seed_len:]
        visible_completion = meta["resume_tokens"] + completion_tokens
        choice: dict[str, Any] = {
            "index": 0,
            "message": {"role": "assistant", "content": text},
            "finish_reason": reason,
        }
        if want_logprobs:
            choice["logprobs"] = {"content": logprob_content}
        return Response.json({
            "id": meta["id"],
            "object": "chat.completion",
            "created": meta["created"],
            "model": meta["model"],
            "choices": [choice],
            "usage": {
                "prompt_tokens": meta["prompt_tokens"],
                "completion_tokens": visible_completion,
                "total_tokens": meta["prompt_tokens"] + visible_completion,
            },
        })

    def _seed_detok(self, meta: dict[str, Any]) -> DetokenizeState:
        """Detokenizer pre-fed with the continuation's resume tokens
        (ISSUE 9): incremental detokenization depends on the preceding
        ids (partial UTF-8 buffering, history rewrites), so a continued
        stream's deltas only match the unkilled run's if the state at
        the splice point is identical. The seed deltas are discarded —
        the client already holds that text."""
        detok = DetokenizeState()
        resume = meta.get("resume_ids") or []
        if resume:
            # Seed in ONE decode pass, not a per-token push() replay —
            # each push() re-decodes the whole id list, which is O(N²)
            # synchronous work on the event loop for a long resume
            # prefix (code-review finding). Final state is identical:
            # ids = the prefix, emitted = its full decode minus the
            # trailing partial-UTF-8 holdback push() applies.
            detok.ids = list(resume)
            text = self.engine.tokenizer.decode(detok.ids)
            while text.endswith("�"):
                text = text[:-1]
            detok.emitted = text
        return detok

    @staticmethod
    def _apply_stop_strings(text: str, stop_strings: list[str], reason: str) -> tuple[str, str]:
        for s in stop_strings:
            if s and s in text:
                return text[: text.index(s)], "stop"
        return text, reason

    def _observe_service(self, seconds: float) -> None:
        self._service.observe(seconds)

    def _retry_after_hint(self) -> int:
        """Seconds until a shed client should retry: observed request
        service time × backlog per decode slot."""
        backlog = self.scheduler.queue_depth + self.scheduler.active_requests() + 1
        return int(self._service.retry_after(backlog, self.engine.config.max_slots))

    def _finalize_request(self, gen: GenRequest, meta: dict[str, Any],
                          traceparent: str | None, completion_tokens: int,
                          stream: bool, finish_reason: str | None) -> None:
        """Per-request observability epilogue (ISSUE 3): materialize the
        queue.wait/prefill/decode child spans from the scheduler's phase
        clock, record the queue-wait sample and output token rate, sample
        engine gauges, and emit the wide-event access-log line. Durations
        degrade gracefully — an abandoned stream may lack later stamps."""
        ph = gen.phase_ns
        submit, admit = ph.get("submit"), ph.get("admit")
        first, finish = ph.get("first_token"), ph.get("finish")
        recv, first_write = ph.get("recv"), ph.get("first_write")

        if submit is not None and admit is not None:
            self.record_queue_wait(max(admit - submit, 0) / 1e9)
        if gen.grammar is not None and self.otel is not None:
            # Constrained-request outcome accounting (ISSUE 13): "stop"
            # here means the grammar (or EOS) completed the document;
            # "length"/"error"/"disconnected" flag truncated or failed
            # constrained streams.
            self.otel.record_constrained_request(
                self.model_name, finish_reason or "unknown")
        if (self.otel is not None and first is not None and finish is not None
                and completion_tokens > 1 and finish > first):
            self.otel.record_output_token_rate(
                "tpu-sidecar", "", "tpu", self.model_name,
                (completion_tokens - 1) / ((finish - first) / 1e9))

        trace_id = ""
        if self.tracer.enabled and submit is not None:
            end_ns = finish or ph.get("first_token") or submit
            root = self.tracer.start_span("tpu_sidecar.chat_completions",
                                          traceparent=traceparent,
                                          start_ns=submit if recv is None else recv)
            trace_id = root.trace_id
            try:
                root.set_attribute("gen_ai.request.model", meta["model"])
                root.set_attribute("gen_ai.provider.name", "tpu")
                root.set_attribute("request.id", gen.request_id or meta["id"])
                root.set_attribute("gen_ai.usage.input_tokens", meta["prompt_tokens"])
                root.set_attribute("gen_ai.usage.output_tokens", completion_tokens)
                phases = (("http.prepare", recv, submit), ("queue.wait", submit, admit),
                          ("prefill", admit, first), ("decode", first, finish),
                          ("first_frame", first, first_write))
                for name, t0, t1 in phases:
                    if t0 is None or t1 is None:
                        continue
                    child = self.tracer.start_span(name, parent=root, start_ns=t0)
                    self.tracer.end_span(child, end_ns=max(t1, t0))
            finally:
                # The root span must reach the exporter even if a child
                # materialization fails mid-loop (graftlint
                # resource-release: spans end on every exception path).
                self.tracer.end_span(root, end_ns=end_ns)

        if not trace_id:
            ctx = parse_traceparent(traceparent)
            trace_id = ctx.trace_id if ctx else ""

        if self.access_log is not None:
            to_ms = lambda a, b: round((b - a) / 1e6, 3) if a is not None and b is not None else None  # noqa: E731
            event = {
                "route": "/v1/chat/completions",
                "provider": "tpu",
                "model": meta["model"],
                "request_id": gen.request_id or meta["id"],
                "trace_id": trace_id or None,
                "stream": stream,
                "finish_reason": finish_reason,
                "input_tokens": meta["prompt_tokens"],
                "output_tokens": completion_tokens,
                "queue_wait_ms": to_ms(submit, admit),
                "prefill_ms": to_ms(admit, first),
                "decode_ms": to_ms(first, finish),
                # The sidecar's own HTTP edges (ISSUE 24): parse, template
                # and tokenise before the scheduler's clock starts, and
                # the hop from the scheduler's first token to the first
                # content frame handed to the transport.
                "recv_to_submit_ms": to_ms(recv, submit),
                "first_token_to_write_ms": to_ms(first, first_write),
            }
            if meta.get("resume_tokens"):
                # Continuation requests (ISSUE 9) are flagged so billing
                # audits can pair a killed stream's line with its
                # continuation's: output_tokens here covers ONLY the new
                # tokens; resume_tokens were billed by the replica that
                # generated them.
                event["resume_tokens"] = meta["resume_tokens"]
            if gen.disconnected:
                event["disconnected"] = True
            self.access_log.emit(event)

        if self.slow_log is not None:
            # Forensics (ISSUE 4): a threshold breach captures the phase
            # clock, trace id, and the engine-step window the request
            # decoded inside — enough to answer "where did the time go"
            # without re-running anything.
            self.slow_log.observe_phases(
                request_id=gen.request_id or meta["id"], trace_id=trace_id,
                model=meta["model"], phase_ns=ph, output_tokens=completion_tokens,
                stream=stream, finish_reason=finish_reason)

        self.sample_engine_gauges()

    async def _stream_chunks(self, gen: GenRequest, meta: dict[str, Any], q: asyncio.Queue,
                             include_usage: bool, arrival: float,
                             traceparent: str | None = None):
        """OpenAI chat.completion.chunk SSE frames off the decode loop.
        The request is already submitted (admission happens in
        chat_completions, where saturation can still become a 429).

        Zero-re-serialization: the invariant chunk envelope
        (id/object/created/model/choices scaffold) is serialized ONCE per
        request; each content frame splices only the JSON-escaped delta
        text between the two halves — byte-identical to a full
        ``json.dumps`` of the envelope, without paying it per token
        (pinned by tests/test_stream_fastpath.py). Rare frames (role
        preamble, finish, usage) still go through format_event."""

        def chunk(delta: dict[str, Any], finish: str | None) -> bytes:
            return sse.format_event({
                "id": meta["id"],
                "object": "chat.completion.chunk",
                "created": meta["created"],
                "model": meta["model"],
                "choices": [{"index": 0, "delta": delta, "finish_reason": finish}],
            })

        prefix = (
            'data: {"id":%s,"object":"chat.completion.chunk","created":%d,'
            '"model":%s,"choices":[{"index":0,"delta":{"content":'
            % (json.dumps(meta["id"]), meta["created"], json.dumps(meta["model"]))
        ).encode()
        suffix = b'},"finish_reason":null}]}\n\n'

        def content_frame(text: str) -> bytes:
            if "first_write" not in gen.phase_ns:
                # The first content frame is about to be handed to the
                # transport: the phase clock's last edge (ISSUE 24).
                gen.phase_ns["first_write"] = time.time_ns()
            return prefix + _json_escape(text).encode() + suffix

        # SERVING_EMIT_COALESCE_MS: merge every token produced within the
        # window into one frame (the queue already delivers one BATCH per
        # decode step, so the window mostly just accepts a whole step's
        # tokens at once instead of emitting one frame per token).
        coalesce_s = self.emit_coalesce
        loop = asyncio.get_running_loop()

        detok = self._seed_detok(meta)
        completion_tokens = 0
        reason = "stop"
        completed = False
        migrated: str | None = None
        try:
            yield chunk({"role": "assistant", "content": ""}, None)

            stop_strings = meta["stop_strings"]
            # A continuation starts past the resume prefix: stop-string
            # scans see the full emitted text (so a stop spanning the
            # kill boundary still cuts), but only new text is framed.
            emitted_len = len(detok.emitted)
            stopped_early = False
            done = False
            while not done and migrated is None:
                item = await q.get()
                migrated = _migrate_signal(item)
                if migrated is not None:
                    # Planned migration (ISSUE 11): stop at this frame
                    # boundary with NO terminal frame — the gateway's
                    # continuation splice resumes the stream on another
                    # replica; tokens already framed here stay billed
                    # here, everything after is the new replica's.
                    break
                batch = list(item)
                if coalesce_s > 0 and not batch[-1][2]:  # last item not finished
                    deadline = loop.time() + coalesce_s
                    while not batch[-1][2]:
                        remaining = deadline - loop.time()
                        if remaining <= 0:
                            break
                        try:
                            nxt = await asyncio.wait_for(q.get(), remaining)
                        except asyncio.TimeoutError:
                            break
                        migrated = _migrate_signal(nxt)
                        if migrated is not None:
                            break
                        batch.extend(nxt)
                parts: list[str] = []
                for token, _logprob, finished, fin_reason in batch:
                    completion_tokens += 1
                    if not (finished and fin_reason == "stop"):
                        delta = detok.push(self.engine.tokenizer, token)
                    else:
                        delta = ""
                    if stop_strings and not stopped_early:
                        cut, new_reason = self._apply_stop_strings(detok.emitted, stop_strings, "")
                        if new_reason == "stop":
                            delta = cut[emitted_len:]
                            stopped_early = True
                            reason = "stop"
                            if delta:
                                emitted_len += len(delta)
                                if coalesce_s > 0:
                                    parts.append(delta)
                                else:
                                    yield content_frame(delta)
                            done = True
                            break
                    if delta and not stopped_early:
                        emitted_len += len(delta)
                        if coalesce_s > 0:
                            parts.append(delta)
                        else:
                            yield content_frame(delta)
                    if finished:
                        reason = fin_reason or "stop"
                        done = True
                        break
                if parts:
                    yield content_frame("".join(parts))

            if migrated is not None:
                # No finish chunk, no usage, no [DONE]: ending inside the
                # content phase is what makes the stream resumable — a
                # terminal frame would disarm the gateway continuation.
                # detok.ids is the exact prompt-relative generated
                # sequence at the cut (seeded resume ids + this
                # replica's pushes, INCLUDING tokens whose text is still
                # held back mid-UTF-8) — published so the new replica
                # resumes byte-identically where text re-encoding would
                # be lossy.
                self._record_migration_resume(meta["id"], detok.ids, migrated)
                reason = "migrated"
                return
            self._observe_service(self._clock.now() - arrival)
            yield chunk({}, reason)
            if include_usage:
                # Usage spans the whole logical stream: resume tokens
                # (billed by the replica that generated them) plus this
                # replica's new tokens — the client-visible frame is
                # byte-identical to an unkilled run's (ISSUE 9).
                visible = meta["resume_tokens"] + completion_tokens
                yield sse.format_event({
                    "id": meta["id"],
                    "object": "chat.completion.chunk",
                    "created": meta["created"],
                    "model": meta["model"],
                    "choices": [],
                    "usage": {
                        "prompt_tokens": meta["prompt_tokens"],
                        "completion_tokens": visible,
                        "total_tokens": meta["prompt_tokens"] + visible,
                    },
                })
            yield sse.DONE_FRAME
            completed = True
        finally:
            # Runs for completed AND abandoned streams (the server
            # acloses the generator on dead clients): phase spans, the
            # queue-wait sample, and the access-log line must not leak.
            self._active_streams.pop(gen.request_id, None)
            if not completed:
                # Abandoned mid-stream: the scheduler decodes on to the
                # finish condition, but those tokens are wasted work —
                # flag the request so the scheduler counts them under
                # engine.wasted_tokens{reason="disconnected"} (ISSUE 6).
                # (A migrated stream was already descheduled by
                # Scheduler.cancel; setting the flag again is harmless.)
                gen.disconnected = True
            self._finalize_request(gen, meta, traceparent, completion_tokens,
                                   stream=True, finish_reason=reason)


async def serve(config: EngineConfig, host: str = "0.0.0.0", port: int = 8000,
                served_model_name: str | None = None, metrics_push_url: str | None = None,
                started_ns: int | None = None) -> None:
    """Run the sidecar until cancelled (entry point for __main__).

    The standalone sidecar honors the gateway's TELEMETRY_* env surface:
    TELEMETRY_TRACING_ENABLE turns on the phase-span tracer (exported to
    TELEMETRY_TRACING_OTLP_ENDPOINT on the push cadence),
    TELEMETRY_ACCESS_LOG the per-request wide-event JSON line, and the
    ISSUE 4 introspection knobs — TELEMETRY_PROFILING_* (sampling
    profiler, event-loop watchdog, decode-step timeline) and
    TELEMETRY_SLOW_REQUEST_* (forensics thresholds).

    ``started_ns`` is ``time.perf_counter_ns()`` at the process's first
    instruction (``serving/__main__.py`` stamps it): the start-up spans
    served under /debug/status ``startup`` count from there."""
    import os

    t_enter = time.perf_counter_ns()  # imports done
    if started_ns is None:
        started_ns = t_enter
    startup: dict[str, float] = {"import_s": (t_enter - started_ns) / 1e9}

    def lap(key: str, since: float) -> float:
        now = time.perf_counter()
        startup[key] = now - since
        return now

    from inference_gateway_tpu.config import (
        ServerConfig,
        ServingConfig,
        StructuredConfig,
        TelemetryConfig,
    )

    tcfg = TelemetryConfig.load(os.environ)
    svcfg = ServingConfig.load(os.environ)
    scfg = ServerConfig.load(os.environ)
    stcfg = StructuredConfig.load(os.environ)
    logger = new_logger()
    # Structured outputs (ISSUE 13): the STRUCTURED_* env surface maps
    # onto the engine's mask-table knobs before the engine is built.
    config.structured = stcfg.enable
    config.structured_states = stcfg.max_states
    config.structured_cache = stcfg.cache_size
    config.structured_max_schema_bytes = stcfg.max_schema_bytes
    # Ragged mixed-step serving (ISSUE 12): on by default for the
    # standalone sidecar wherever the engine supports it (paged,
    # non-speculative — Engine.mixed_ok gates the rest). The scheduler
    # then interleaves chunked prefill with decode in the same engine
    # step, and paged engines admit prompts up to the context window.
    if svcfg.mixed_step_enable and config.attention == "paged":
        config.mixed_step = True
        if svcfg.mixed_step_tokens:
            config.mixed_step_tokens = svcfg.mixed_step_tokens
    # Desynchronized decode (ISSUE 14): SERVING_DECODE_* maps onto the
    # engine's chunk-size / pipeline-depth knobs before the engine is
    # built. 0 keeps the engine defaults.
    if svcfg.decode_chunk:
        config.decode_chunk = svcfg.decode_chunk
    if svcfg.decode_pipeline_depth:
        config.pipeline_depth = svcfg.decode_pipeline_depth
    # Before anything compiles: a restart then loads its programs
    # instead of compiling them.
    compile_cache = configure_compile_cache()
    engine = Engine(config)
    startup.update(engine.startup)
    t_stage = time.perf_counter()
    # Device observatory (ISSUE 19): attach BEFORE warmup so every boot
    # compile lands in the ledger — warmup() brackets itself, so these
    # classify as warmup, not recompiles.
    observatory = None
    if tcfg.device_enable:
        try:
            observatory = DeviceObservatory(
                model=served_model_name or config.model, logger=logger,
                ledger_size=tcfg.device_ledger_size)
            observatory.attach(engine)
        except Exception as e:
            logger.warn("device observatory disabled", "error", str(e))
            observatory = None
    t_stage = lap("observatory_s", t_stage)
    warm = engine.warmup()
    t_stage = lap("warmup_s", t_stage)
    logger.info("engine warm", "compile_seconds", round(warm, 1), "model", config.model,
                "attention_path", engine.attention_path, "compile_cache", compile_cache,
                "accelerator", engine.device,
                "startup", {k: round(v, 3) for k, v in startup.items()})
    tracer = None
    if tcfg.tracing_enable:
        tracer = Tracer("tpu-sidecar", enabled=True, logger=logger,
                        otlp_endpoint=tcfg.tracing_otlp_endpoint)
    access_log = None
    if tcfg.access_log:
        from inference_gateway_tpu.otel.access_log import AccessLog

        access_log = AccessLog(service="tpu-sidecar", tail_size=tcfg.access_log_tail)
    profiler = None
    if tcfg.profiling_enable or tcfg.profiling_continuous:
        from inference_gateway_tpu.otel.profiling import SamplingProfiler

        profiler = SamplingProfiler(
            hz=tcfg.profiling_hz, window_s=tcfg.profiling_window,
            windows=tcfg.profiling_windows, max_stacks=tcfg.profiling_max_stacks,
            logger=logger)
        if tcfg.profiling_continuous:
            profiler.start_continuous()
    watchdog = None
    if tcfg.profiling_watchdog:
        from inference_gateway_tpu.otel.profiling import EventLoopWatchdog

        watchdog = EventLoopWatchdog(
            access_log=access_log, interval=tcfg.profiling_watchdog_interval,
            threshold=tcfg.profiling_watchdog_threshold, source="tpu-sidecar",
            logger=logger)
    slow_log = SlowRequestLog(
        ttft_s=tcfg.slow_request_ttft, tpot_s=tcfg.slow_request_tpot,
        total_s=tcfg.slow_request_total, size=tcfg.slow_request_log_size,
        source="tpu-sidecar")
    engine_watchdog = None
    if svcfg.watchdog_enable:
        from inference_gateway_tpu.serving.watchdog import EngineWatchdog

        engine_watchdog = EngineWatchdog(
            interval=svcfg.watchdog_interval,
            multiplier=svcfg.watchdog_multiplier,
            min_deadline=svcfg.watchdog_min_deadline, logger=logger)
    # KV-pressure preemption only means anything with a page pool to
    # exhaust: a dense (non-paged) engine can never raise
    # OutOfPagesError in production, so don't pay the per-token resume
    # bookkeeping there (code-review finding).
    preempt_budget = (svcfg.preempt_budget
                      if svcfg.preempt_enable and engine.allocator is not None else 0)
    server = SidecarServer(engine, served_model_name=served_model_name, logger=logger,
                           metrics_push_url=metrics_push_url, tracer=tracer,
                           access_log=access_log,
                           timeline_size=tcfg.profiling_timeline_size,
                           slow_log=slow_log, profiler=profiler, watchdog=watchdog,
                           emit_coalesce=svcfg.emit_coalesce,
                           stream_coalesce=scfg.stream_coalesce,
                           observatory=observatory,
                           device_enable=tcfg.device_enable,
                           device_ledger_size=tcfg.device_ledger_size,
                           preempt_max=preempt_budget,
                           preempt_high_water=svcfg.preempt_high_water,
                           engine_watchdog=engine_watchdog,
                           migrate_streams=svcfg.migrate_streams,
                           admin_enabled=svcfg.admin_enabled)
    bound = await server.start(host, port)
    lap("listen_s", t_stage)
    startup["total_s"] = (time.perf_counter_ns() - started_ns) / 1e9
    server.startup = {k: round(v, 4) for k, v in startup.items()}
    logger.info("tpu sidecar listening", "host", host, "port", bound,
                "listen_s", server.startup["listen_s"], "total_s", server.startup["total_s"])
    try:
        await asyncio.Event().wait()
    finally:
        await server.shutdown()
