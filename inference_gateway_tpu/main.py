"""Gateway assembly and lifecycle.

Capability parity with reference cmd/gateway/main.go:36-344: config load →
logger → otel init (+ dedicated metrics listener on
TELEMETRY_METRICS_PORT) → middleware chain (tracing → logger → telemetry →
auth → mcp; order fixed, MCP last so it sees the authenticated, measured
request) → self-addressing HTTP client → provider registry → MCP
client/agent → routing selector → router → API server, with an async
startup provider-validation pass and graceful shutdown.
"""

from __future__ import annotations

import asyncio
import signal
import time
from dataclasses import dataclass, field
from typing import Any

from inference_gateway_tpu.api.middlewares.auth import OIDCAuthenticator, oidc_auth_middleware
from inference_gateway_tpu.api.middlewares.logger import logger_middleware
from inference_gateway_tpu.api.middlewares.telemetry import (
    journey_shed_middleware,
    telemetry_middleware,
    traceparent_relay_middleware,
    tracing_middleware,
)
from inference_gateway_tpu.api.routes import RouterImpl, Response
from inference_gateway_tpu.cluster.shm import ClusterSegment, PeerHealthView, WorkerSlab
from inference_gateway_tpu.cluster.tenancy import TenantPolicy
from inference_gateway_tpu.cluster.worker import WorkerRuntime
from inference_gateway_tpu.config import Config
from inference_gateway_tpu import native
from inference_gateway_tpu.logger import Logger, new_logger
from inference_gateway_tpu.netio.client import ClientConfig, HTTPClient
from inference_gateway_tpu.netio.server import HTTPServer, Request, Router
from inference_gateway_tpu.otel import OpenTelemetry
from inference_gateway_tpu.otel.journey import JourneyRecorder
from inference_gateway_tpu.otel.slo import SloTracker
from inference_gateway_tpu.otel.profiling import (
    EventLoopWatchdog,
    SamplingProfiler,
    SlowRequestLog,
    handle_profile_query,
)
from inference_gateway_tpu.providers import constants, routing
from inference_gateway_tpu.providers.registry import ProviderRegistry
from inference_gateway_tpu.resilience import OverloadController, Resilience, admission_middleware
from inference_gateway_tpu.version import APPLICATION_NAME, VERSION


@dataclass
class Gateway:
    """A fully-wired gateway instance plus its listeners."""

    cfg: Config
    logger: Logger
    otel: OpenTelemetry | None
    registry: ProviderRegistry
    client: HTTPClient
    router_impl: RouterImpl
    api_server: HTTPServer
    metrics_server: HTTPServer | None = None
    mcp_client: Any = None
    overload: OverloadController | None = None
    resilience: Any = None
    prober: Any = None
    migrator: Any = None
    access_log: Any = None
    profiler: SamplingProfiler | None = None
    watchdog: EventLoopWatchdog | None = None
    slow_log: SlowRequestLog | None = None
    cluster_segment: ClusterSegment | None = None
    cluster_slab: WorkerSlab | None = None
    cluster_runtime: WorkerRuntime | None = None
    journeys: JourneyRecorder | None = None
    slo: SloTracker | None = None
    port: int = 0
    metrics_port: int = 0
    _tasks: list[asyncio.Task] = field(default_factory=list)
    _started: float = field(default_factory=time.monotonic)

    async def start(self, host: str | None = None, port: int | None = None) -> int:
        host = host or self.cfg.server.host
        port = int(port if port is not None else self.cfg.server.port)
        # Cluster workers share both listener ports via SO_REUSEPORT (the
        # kernel balances accepts; a respawn rebinds while siblings keep
        # the port open). Single-process mode binds exactly as before.
        reuse_port = self.cluster_slab is not None
        if self.metrics_server is not None:
            self.metrics_port = await self.metrics_server.start(
                host, int(self.cfg.telemetry.metrics_port), reuse_port=reuse_port
            )
            self.logger.info("metrics server listening", "port", self.metrics_port)
        if self.mcp_client is not None:
            await self.mcp_client.initialize_all()
            self.mcp_client.start_status_polling()
        self.port = await self.api_server.start(
            host, port, self.cfg.server.tls_cert_path, self.cfg.server.tls_key_path,
            reuse_port=reuse_port,
        )
        if self.cluster_runtime is not None:
            # First heartbeat the moment the listener is up: the
            # supervisor's staleness clock starts at spawn.
            self.cluster_runtime.start()
        # Performance introspection (ISSUE 4): the continuous sampler is
        # a daemon thread, the watchdog heartbeat a loop task — both
        # started here (the loop exists now) and torn down in shutdown().
        if self.profiler is not None and self.cfg.telemetry.profiling_continuous:
            self.profiler.start_continuous()
        if self.watchdog is not None:
            self.watchdog.start()
        if self.prober is not None:
            # Active pool health probing (ISSUE 9): per-deployment
            # /health loop — starts here (the loop exists now), torn
            # down in shutdown().
            self.prober.start()
        # Self-addressing: the provider loopback hop targets this listener
        # (main.go:167, client.go:66-75).
        self.client.self_host = "127.0.0.1" if host in ("0.0.0.0", "") else host
        self.client.self_port = self.port
        self.logger.info("gateway listening", "app", APPLICATION_NAME, "version", VERSION,
                         "host", host, "port", self.port, "framing", native.status)
        self._tasks.append(asyncio.create_task(self._validate_providers()))
        return self.port

    async def _validate_providers(self) -> None:
        """Async startup validation: log-only ListModels per configured
        provider (main.go:295-324)."""
        for pid, pcfg in self.registry.get_providers().items():
            if pcfg.auth_type != "none" and not pcfg.token:
                continue
            try:
                provider = self.registry.build_provider(pid, self.client)
                await asyncio.wait_for(provider.list_models(), timeout=10.0)
                self.logger.info("provider validated", "provider", pid)
            except Exception as e:
                self.logger.warn("provider validation failed", "provider", pid, "error", str(e))

    async def shutdown(self) -> None:
        """Graceful drain (ISSUE 2): readiness flips first (health 503s,
        new work rejected fast by the admission middleware), then the
        listener stays open while in-flight requests — including SSE
        streams — finish within DRAIN_DEADLINE, and only then are
        sockets torn down."""
        for t in self._tasks:
            t.cancel()
        if self.watchdog is not None:
            # The heartbeat would read every drain pause as a stall.
            await self.watchdog.stop()
        if self.prober is not None:
            await self.prober.stop()
        if self.overload is not None:
            self.overload.begin_drain()
        if self.mcp_client is not None:
            await self.mcp_client.shutdown()
        await self.api_server.shutdown(
            drain=self.cfg.overload.drain_deadline if self.overload is not None else 0.0,
            ledger=self.overload,
        )
        if self.metrics_server is not None:
            await self.metrics_server.shutdown()
        if self.profiler is not None:
            self.profiler.stop()
        if self.cluster_runtime is not None:
            await self.cluster_runtime.stop()
        if self.cluster_segment is not None:
            # Detach only: the supervisor owns the segment's lifetime
            # and reaps this worker's slab once the process exits.
            self.cluster_segment.close()
        self.logger.info("gateway stopped")


def build_gateway(cfg: Config | None = None, env: dict[str, str] | None = None,
                  logger: Logger | None = None, mcp_client=None, mcp_agent=None) -> Gateway:
    if cfg is None:
        cfg = Config.load(env, logger=logger)
    logger = logger or new_logger(cfg.environment)

    # Cluster worker mode (ISSUE 16): the supervisor spawned this process
    # with a segment handshake in the environment — attach the shared
    # segment and claim our slab. Absent the handshake (the default),
    # nothing below changes: no segment, no mirror writes, no REUSEPORT.
    cluster_segment = None
    cluster_slab = None
    peer_health = None
    if cfg.cluster.segment_name and cfg.cluster.worker_index >= 0:
        cluster_segment = ClusterSegment.attach(
            cfg.cluster.segment_name, workers=max(1, cfg.cluster.workers),
            tenant_slots=cfg.cluster.tenant_slots,
            journey_slots=cfg.telemetry.journey_slots,
            journey_slot_bytes=cfg.telemetry.journey_slot_bytes)
        cluster_slab = cluster_segment.slab(cfg.cluster.worker_index)
        # Cached peer-verdict merge for the routing hot path — refreshed
        # by the WorkerRuntime on the heartbeat interval, read per
        # candidate as a set lookup (never a per-request blob decode).
        peer_health = PeerHealthView(cluster_segment, cfg.cluster.worker_index)
        logger.info("cluster worker attached", "segment", cfg.cluster.segment_name,
                    "worker", cfg.cluster.worker_index,
                    "generation", cluster_slab.generation)

    # Per-tenant isolation policy (ISSUE 16): built unconditionally (the
    # admission edge and ledger consult .enabled), weights/quotas from
    # TENANT_*.
    tenancy = TenantPolicy(cfg.tenant)

    otel = None
    metrics_server = None
    metrics_router = None
    profiler = None
    watchdog = None
    slow_log = None
    if cfg.telemetry.enable:
        otel = OpenTelemetry(
            environment=cfg.environment,
            tracing_enable=cfg.telemetry.tracing_enable,
            tracing_otlp_endpoint=cfg.telemetry.tracing_otlp_endpoint,
            logger=logger,
        )

        def merged_slo_counts():
            """Cluster-merged SLO window counts at scrape time (ISSUE
            18): publish OUR freshest counts first, then sum every live
            worker's published counts — so the burn-rate gauges read
            identically from any worker, modulo one heartbeat."""
            if slo_tracker is None:
                return None
            if cluster_runtime is not None:
                cluster_runtime.publish_once()
            if cluster_segment is not None:
                payloads = [blob.get("slo")
                            for blob in cluster_segment.blobs().values()]
                return SloTracker.merge_payloads([p for p in payloads if p])
            return None

        async def prometheus_handler(req: Request) -> Response:
            if slo_tracker is not None:
                # Refresh the slo.* gauges from the fleet merge (local
                # windows when single-process) just before exposition.
                slo_tracker.export(otel, merged_slo_counts())
            body = otel.expose_prometheus()
            if cluster_segment is not None:
                # Per-worker metric merge (ISSUE 16): whichever worker
                # the scrape lands on, the cluster_* series (live
                # workers, heartbeat ages, summed admission ledger) are
                # identical — read straight from the shared segment.
                body += cluster_segment.render_prometheus(resilience.clock.now())
            return Response.text(body, content_type="text/plain; version=0.0.4")

        metrics_router = Router()
        metrics_router.get("/metrics", prometheus_handler)
        # /debug/status is registered below, once the breaker registry
        # and admission ledger it snapshots exist.
        metrics_server = HTTPServer(metrics_router, logger=logger)

        # Performance introspection (ISSUE 4): a sampling profiler
        # (on-demand /debug/profile captures; TELEMETRY_PROFILING_CONTINUOUS
        # keeps a ring of recent windows), an event-loop stall watchdog,
        # and slow-request forensics at the gateway edge — all off by
        # default and zero-overhead when off.
        t = cfg.telemetry
        if t.profiling_enable or t.profiling_continuous:
            profiler = SamplingProfiler(
                hz=t.profiling_hz, window_s=t.profiling_window,
                windows=t.profiling_windows, max_stacks=t.profiling_max_stacks,
                logger=logger)
        slow_log = SlowRequestLog(
            ttft_s=t.slow_request_ttft, tpot_s=t.slow_request_tpot,
            total_s=t.slow_request_total, size=t.slow_request_log_size,
            otel=otel, source="gateway")
        if t.profiling_watchdog:
            watchdog = EventLoopWatchdog(
                otel=otel, interval=t.profiling_watchdog_interval,
                threshold=t.profiling_watchdog_threshold, source="gateway",
                logger=logger)

    client = HTTPClient(
        ClientConfig(
            timeout=cfg.client.timeout,
            max_idle_conns_per_host=cfg.client.max_idle_conns_per_host,
            idle_conn_timeout=cfg.client.idle_conn_timeout,
            disable_compression=cfg.client.disable_compression,
        ),
        self_host="127.0.0.1",
        self_port=int(cfg.server.port),
    )
    registry = ProviderRegistry(cfg.providers, logger=logger)

    # Resilience layer (ISSUE 1): one breaker registry + retry policy per
    # gateway, shared by the routing selector (health-aware candidate
    # ordering) and every handler (failover/retry/deadline budgets).
    resilience = Resilience(cfg.resilience, otel=otel, logger=logger)

    # Fleet observability plane (ISSUE 18): the journey recorder mirrors
    # stream lifecycles into this worker's reap-surviving shm journey
    # slots (clustered) so any worker answers /debug/journey — including
    # for hops served by workers that have since died; the SLO tracker
    # keeps per-tenant/per-pool sliding-window SLIs whose counts ride
    # the heartbeat blob for cluster-identical burn rates.
    journeys = None
    slo_tracker = None
    if otel is not None and cfg.telemetry.journey_enable:
        journeys = JourneyRecorder(
            slab=cluster_slab, worker=max(0, cfg.cluster.worker_index),
            clock=resilience.clock, max_journeys=cfg.telemetry.journey_slots,
            max_events=cfg.telemetry.journey_events,
            slot_bytes=cfg.telemetry.journey_slot_bytes, otel=otel)
        resilience.journeys = journeys
    if otel is not None and cfg.slo.enabled:
        slo_tracker = SloTracker(
            availability_target=cfg.slo.availability_target,
            ttft_threshold=cfg.slo.ttft_threshold,
            ttft_target=cfg.slo.ttft_target,
            tpot_threshold=cfg.slo.tpot_threshold,
            tpot_target=cfg.slo.tpot_target,
            max_tenant_series=cfg.slo.max_tenant_series,
            clock=resilience.clock)

    # Overload protection (ISSUE 2): one admission ledger per gateway —
    # the admission middleware, the health handler (readiness), and
    # shutdown (graceful drain) all coordinate through it. Clustered
    # (ISSUE 16), every ledger mutation is mirrored into this worker's
    # shared slab and tenant quota/fairness policy rides the same admit
    # path.
    overload = OverloadController(cfg.overload, otel=otel, logger=logger,
                                  tenancy=tenancy, shared=cluster_slab)

    selector = None
    prober = None
    migrator = None
    fleet_urls: dict[str, set[str]] = {}
    if cfg.routing.enabled:
        if not cfg.routing.config_path:
            raise ValueError("ROUTING_CONFIG_PATH is required when ROUTING_ENABLED is true")
        pools = routing.load_pools_config(cfg.routing.config_path)

        def deployment_url(d) -> str:
            # Per-deployment base URL override (ISSUE 11) or the
            # provider default — the replica's actual home.
            return d.url or cfg.providers[d.provider].url

        for pool in pools.values():
            for d in pool.deployments:
                if d.url:
                    fleet_urls.setdefault(d.provider, set()).add(d.url)
        # Active pool health probing (ISSUE 9): a background /health
        # probe per pool deployment ejects dead replicas after K
        # consecutive failures — the selector demotes them AND the
        # executor skips them outright (zero establishment attempts)
        # until a probe succeeds again. Passive breaker health still
        # covers direct (non-pool) routes. The probe body doubles as the
        # fleet load report (ISSUE 11): queue depth / KV utilization /
        # slot occupancy feed the router's bounded-load spill.
        health = resilience.healthy
        if cfg.resilience.enabled and cfg.resilience.probe_enabled:
            from inference_gateway_tpu.resilience.prober import (
                HealthProber,
                ProbeTarget,
                probe_url,
            )

            targets = [
                ProbeTarget(d.provider, d.model, probe_url(deployment_url(d)))
                for pool in pools.values() for d in pool.deployments
            ]
            prober = HealthProber(
                targets, client, interval=cfg.resilience.probe_interval,
                timeout=cfg.resilience.probe_timeout,
                eject_after=cfg.resilience.probe_failures,
                collect_status=True,
                otel=otel, logger=logger)
            resilience.prober = prober

            def health(d, _breakers=resilience.healthy, _probes=prober.healthy):
                return _breakers(d) and _probes(d.provider, d.model)

        # Fleet migrator (ISSUE 11 tentpole b): gateway-side drain
        # coordination + planned-migration attribution. A draining
        # deployment leaves the healthy ordering the moment the operator
        # asks, and its live streams' deaths are counted (and breaker-
        # exempted) as migrations, not failures.
        from inference_gateway_tpu.fleet import FleetMigrator, FleetRouter

        all_deployments = [d for pool in pools.values() for d in pool.deployments]
        migrator = FleetMigrator(
            {(d.provider, d.model): deployment_url(d) for d in all_deployments},
            client,
            # Only the TPU sidecar speaks the /admin surface: foreign
            # cloud deployments are drainable at the routing level but
            # never receive /admin/* requests or completion ids.
            admin_keys={(d.provider, d.model) for d in all_deployments
                        if d.provider == constants.TPU_ID},
            otel=otel, logger=logger, clock=resilience.clock)
        resilience.migrator = migrator

        def fleet_health(d, _h=health, _m=migrator, _peers=peer_health):
            if not _h(d) or _m.draining(d.provider, d.model):
                return False
            # Cross-worker health merge (ISSUE 16): peers' published
            # probe verdicts can only REMOVE a candidate — one confused
            # worker can never readmit a replica the rest of the cluster
            # has condemned, and a worker with no local evidence still
            # avoids a replica its peers know is dead. The view is a
            # heartbeat-interval cache: a set lookup here, not a
            # per-candidate decode of every peer's blob.
            if _peers is not None and _peers.ejected(d.provider, d.model):
                return False
            return True

        # Fleet router (ISSUE 11 tentpole a): prefix-affinity consistent-
        # hash ordering with bounded-load spill; keyless requests (and
        # ROUTING_AFFINITY_ENABLED=false) keep round-robin.
        selector = FleetRouter(
            pools, health=fleet_health,
            load=(prober.load if prober is not None else None),
            affinity_enabled=cfg.routing.affinity_enabled,
            affinity_prefix_bytes=cfg.routing.affinity_prefix_bytes,
            vnodes=cfg.routing.affinity_vnodes,
            spill_queue_depth=cfg.routing.spill_queue_depth,
            spill_kv_high_water=cfg.routing.spill_kv_high_water,
            otel=otel, logger=logger)
        # Pool-level admission (ISSUE 11 tentpole c): the cluster's
        # minimum reported scheduler backlog feeds shedding
        # (OVERLOAD_ENGINE_DEPTH_HIGH_WATER) and Retry-After hints, so
        # overload decisions see the fleet, not one process.
        overload.add_depth_probe(selector.cluster_queue_depth)
        logger.info("fleet routing pools loaded", "aliases", selector.aliases(),
                    "affinity", cfg.routing.affinity_enabled,
                    "active_probing", prober is not None,
                    "fleet_urls", sum(len(v) for v in fleet_urls.values()))

    # MCP subsystem (main.go:181-213).
    if mcp_client is None and cfg.mcp.enable and cfg.mcp.servers:
        from inference_gateway_tpu.mcp.agent import Agent
        from inference_gateway_tpu.mcp.client import MCPClient

        mcp_client = MCPClient(cfg.mcp, client, logger=logger)
        mcp_agent = Agent(mcp_client, logger=logger, otel=otel)

    router_impl = RouterImpl(
        cfg, registry, client, logger=logger, otel=otel,
        mcp_client=mcp_client, mcp_agent=mcp_agent, selector=selector,
        resilience=resilience, overload=overload, fleet_urls=fleet_urls,
        journeys=journeys,
    )

    # Middleware order matters (main.go:238-254): the wide-event access
    # log is outermost so even shed requests leave one JSON line (ISSUE
    # 3) — it is the one observability cost a rejected request pays —
    # then admission (everything else costs nothing for a shed request:
    # no span, no log line, no auth round trip), then tracing → logger →
    # telemetry → auth → mcp. MCP must be last.
    access_log = None
    middlewares = []
    if cfg.telemetry.access_log:
        from inference_gateway_tpu.otel.access_log import AccessLog, access_log_middleware

        access_log = AccessLog(service=APPLICATION_NAME,
                               tail_size=cfg.telemetry.access_log_tail)
        middlewares.append(access_log_middleware(access_log))
    if watchdog is not None:
        # Stall wide events ride the access-log sink when it exists.
        watchdog.access_log = access_log
    if journeys is not None:
        # Outside admission (ISSUE 18): sheds short-circuit before any
        # span exists, so their journey events are recorded here, keyed
        # by the client's inbound traceparent.
        middlewares.append(journey_shed_middleware(journeys, slo=slo_tracker))
    middlewares.append(admission_middleware(overload, logger, tenancy=tenancy))
    if otel is not None and cfg.telemetry.tracing_enable:
        middlewares.append(tracing_middleware(otel.tracer))
    else:
        middlewares.append(traceparent_relay_middleware())
    middlewares.append(logger_middleware(logger))
    if otel is not None:
        # The telemetry middleware doubles as the gateway-edge forensics
        # feeder: it measures TTFC/duration/rate for every inference
        # request regardless of whether the access log is on, so the
        # TELEMETRY_SLOW_REQUEST_* thresholds work standalone.
        middlewares.append(telemetry_middleware(otel, logger, slow_log=slow_log,
                                                journeys=journeys,
                                                slo=slo_tracker))
    authenticator = None
    if cfg.auth.enable:
        authenticator = OIDCAuthenticator(
            cfg.auth.oidc_issuer, cfg.auth.oidc_client_id, client, logger=logger
        )
    # The auth middleware feeds the tenancy policy each verified token's
    # subject, so the pre-auth tenant derivation can use sub buckets
    # without ever trusting an unverified claim (forged subs bucket by
    # token digest instead — they can never burn a victim's quota).
    middlewares.append(oidc_auth_middleware(authenticator, logger, tenancy=tenancy))
    if mcp_client is not None and mcp_agent is not None:
        from inference_gateway_tpu.api.middlewares.mcp import mcp_middleware

        middlewares.append(mcp_middleware(mcp_client, mcp_agent, registry, client, cfg, logger))

    api_server = HTTPServer(
        router_impl.build_router(),
        middlewares=middlewares,
        read_timeout=cfg.server.read_timeout,
        write_timeout=cfg.server.write_timeout,
        idle_timeout=cfg.server.idle_timeout,
        logger=logger,
        stream_coalesce=cfg.server.stream_coalesce,
    )
    # Self-addressed (relative-URL) requests — the provider layer's
    # /proxy/ double hop — dispatch in-process through this server's
    # router + middleware chain instead of a loopback TCP round trip.
    client.inprocess_server = api_server

    if watchdog is not None:
        # Forensic context stamped onto every stall event: how many live
        # connections each listener was holding when the loop wedged.
        watchdog.add_context("api_connections", api_server.connection_count)
        if metrics_server is not None:
            watchdog.add_context("metrics_connections", metrics_server.connection_count)

    cluster_runtime = None
    if cluster_slab is not None:
        # Heartbeat + verdict publisher: beats the slab on the interval
        # the supervisor's staleness check expects, and publishes local
        # prober/breaker verdicts for peers to read-merge.
        cluster_runtime = WorkerRuntime(
            cluster_slab, prober=prober, breakers=resilience.breakers,
            peer_health=peer_health, slo=slo_tracker, migrator=migrator,
            interval=cfg.cluster.heartbeat_interval, clock=resilience.clock,
            logger=logger)

    gw = Gateway(
        cfg=cfg, logger=logger, otel=otel, registry=registry, client=client,
        router_impl=router_impl, api_server=api_server, metrics_server=metrics_server,
        mcp_client=mcp_client, overload=overload, resilience=resilience,
        prober=prober, migrator=migrator, access_log=access_log,
        profiler=profiler, watchdog=watchdog, slow_log=slow_log,
        cluster_segment=cluster_segment, cluster_slab=cluster_slab,
        cluster_runtime=cluster_runtime, journeys=journeys, slo=slo_tracker,
    )
    # Uptime reads through the resilience clock (graftlint
    # clock-discipline): stamp the start on the same timebase.
    gw._started = resilience.clock.now()

    if metrics_router is not None:
        # /debug/status (ISSUE 3): one JSON snapshot for humans and
        # probes — build info, breaker states, the admission ledger, and
        # every live gauge point (engine occupancy/KV pressure when a
        # sidecar is co-hosted, breaker codes, overload in-flight) —
        # extended (ISSUE 4) with profiler/watchdog health and the
        # slow-request log.
        async def debug_status_handler(req: Request) -> Response:
            status: dict[str, Any] = {
                "app": APPLICATION_NAME,
                "version": VERSION,
                "environment": cfg.environment,
                "uptime_seconds": round(resilience.clock.now() - gw._started, 3),
                "breakers": resilience.breaker_snapshot(),
                "admission": overload.snapshot(),
                "gauges": otel.registry.gauge_snapshot(),
            }
            if prober is not None:
                status["probes"] = prober.snapshot()
            if selector is not None and hasattr(selector, "snapshot"):
                # Fleet routing snapshot (ISSUE 11): ring layout,
                # per-deployment health/saturation/load, and the drain
                # ledger — the operator's one-stop view of the data
                # plane.
                status["routing"] = selector.snapshot()
            if migrator is not None:
                status["migration"] = migrator.snapshot()
            if access_log is not None:
                status["access_log_tail"] = list(access_log.tail)[-8:]
                status["access_log_dropped"] = access_log.dropped
            if slow_log is not None:
                status["slow_requests"] = slow_log.snapshot()
            if profiler is not None:
                status["profiling"] = profiler.stats()
            if watchdog is not None:
                status["eventloop"] = watchdog.stats()
            if cluster_segment is not None:
                # Cluster view (ISSUE 16): live workers, heartbeat ages,
                # per-worker admission cells, cluster-wide sums —
                # identical from whichever worker answered the scrape.
                status["cluster"] = cluster_segment.status(resilience.clock.now())
                status["cluster"]["self_worker"] = cfg.cluster.worker_index
            if journeys is not None:
                status["journeys"] = journeys.snapshot()
            if slo_tracker is not None:
                # Cluster-merged burn rates (ISSUE 18) — same numbers
                # /metrics exposes, in snapshot form.
                status["slo"] = slo_tracker.snapshot(merged_slo_counts())
            return Response.json(status)

        metrics_router.get("/debug/status", debug_status_handler)

        if journeys is not None:
            # /debug/journey?trace_id= (ISSUE 18 tentpole b): the merged
            # cross-worker journey for one trace — answered from ANY
            # worker because the shm journey slots survive worker death
            # and respawn; the admit→route→kill→splice→finish chain of a
            # stream whose original worker was SIGKILLed reads back whole
            # from any survivor.
            async def debug_journey_handler(req: Request) -> Response:
                trace_id = req.query_get("trace_id")
                if not trace_id:
                    return Response.json(
                        {"error": "trace_id query param required"}, status=400)
                rec = journeys.lookup(trace_id)
                if rec is None:
                    return Response.json(
                        {"error": f"no journey recorded for trace {trace_id}",
                         "trace_id": trace_id}, status=404)
                return Response.json(rec)

            metrics_router.get("/debug/journey", debug_journey_handler)

        # /debug/fleet (ISSUE 18 tentpole a): the cluster-merged
        # operator pane — every worker's slab (heartbeats, admission
        # cells, published probe/breaker verdicts, migration ledgers),
        # every pool replica's cached /debug/status + load report (off
        # the prober's poll cadence — no new request-path traffic), the
        # routing/drain state, SLO burn rates, and recent journeys — one
        # GET, any worker, same answer.
        async def debug_fleet_handler(req: Request) -> Response:
            now = resilience.clock.now()
            fleet: dict[str, Any] = {
                "app": APPLICATION_NAME,
                "version": VERSION,
                "environment": cfg.environment,
            }
            if cluster_segment is not None:
                fleet["cluster"] = cluster_segment.status(now)
                fleet["cluster"]["self_worker"] = cfg.cluster.worker_index
                # Per-worker published payloads: probe/breaker verdicts,
                # migration ledgers — whatever each worker last
                # heartbeat-published.
                fleet["workers"] = {
                    str(i): blob
                    for i, blob in sorted(cluster_segment.blobs().items())}
            else:
                fleet["cluster"] = None
            fleet["admission"] = overload.snapshot()
            if prober is not None:
                # Replica pane: verdicts + load reports + the cached
                # ?brief=1 debug-status of every pool replica.
                fleet["replicas"] = prober.snapshot()
            if selector is not None and hasattr(selector, "snapshot"):
                fleet["routing"] = selector.snapshot()
            if migrator is not None:
                fleet["migration"] = migrator.snapshot()
            if slo_tracker is not None:
                fleet["slo"] = slo_tracker.snapshot(merged_slo_counts())
            if journeys is not None:
                fleet["journeys"] = journeys.snapshot()
            return Response.json(fleet)

        metrics_router.get("/debug/fleet", debug_fleet_handler)

        # /debug/profile (ISSUE 4): flamegraph-ready collapsed stacks —
        # on-demand capture (?seconds=N&hz=M) or the continuous ring
        # (?mode=continuous).
        async def debug_profile_handler(req: Request) -> Response:
            status, ctype, body = await handle_profile_query(
                profiler, seconds=req.query_get("seconds"),
                hz=req.query_get("hz"), mode=req.query_get("mode"))
            return Response.text(body, status=status, content_type=ctype)

        metrics_router.get("/debug/profile", debug_profile_handler)

        if migrator is not None:
            # Fleet drain orchestration (ISSUE 11): POST
            # /debug/fleet/drain?provider=tpu&model=llama@a marks the
            # deployment draining (instant routing demotion) and tells
            # its sidecar to migrate live streams out; undrain reverses
            # it. On the metrics listener: operator surface, not data
            # plane.
            def _fleet_admin(action):
                async def handler(req: Request) -> Response:
                    provider = req.query_get("provider")
                    model = req.query_get("model")
                    if not provider or not model:
                        return Response.json(
                            {"error": "provider and model query params required"},
                            status=400)
                    try:
                        result = await action(provider, model)
                    except KeyError:
                        return Response.json(
                            {"error": f"unknown fleet deployment {provider}/{model}"},
                            status=404)
                    return Response.json(result)

                return handler

            metrics_router.post("/debug/fleet/drain", _fleet_admin(migrator.drain))
            metrics_router.post("/debug/fleet/undrain", _fleet_admin(migrator.undrain))

    return gw


async def run() -> None:
    """Run until SIGINT/SIGTERM (main.go:326-343).

    CLUSTER_WORKERS > 1 turns this process into the supervisor: it
    creates the shared segment and forks that many gateway workers
    (each re-entering here WITH the segment handshake set, so they take
    the normal serving path below on SO_REUSEPORT listeners)."""
    cfg = Config.load()
    if cfg.cluster.workers > 1 and not cfg.cluster.segment_name:
        from inference_gateway_tpu.cluster.supervisor import run_supervisor

        await run_supervisor(cfg, new_logger(cfg.environment))
        return
    gw = build_gateway(cfg)
    await gw.start()
    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        loop.add_signal_handler(sig, stop.set)
    await stop.wait()
    # Shutdown is drain-aware: allow the configured drain window plus a
    # margin for socket teardown before giving up.
    await asyncio.wait_for(gw.shutdown(), timeout=gw.cfg.overload.drain_deadline + 10.0)


def main() -> None:
    import sys

    if "--version" in sys.argv:
        print(f"{APPLICATION_NAME} {VERSION}")
        return
    asyncio.run(run())


if __name__ == "__main__":
    main()
