"""Instrument-registration lint + noop drift guard (ISSUE 4 satellites).

Walks every instrument registered in ``OpenTelemetry.__init__`` and
asserts the conventions the Prometheus exposition depends on: names
sanitize idempotently into valid Prometheus identifiers, no duplicate
registrations, label names exposition-safe, histogram boundaries
strictly increasing, and unit-suffix naming conventions. Separately
asserts ``NoopTelemetry`` overrides every public recorder — PR 3 added
five recorders by hand, and a new one silently running the real
implementation in noop mode is exactly the regression this guards.
"""

import re

from inference_gateway_tpu.otel.metrics import Counter, Gauge, Histogram, _sanitize_name
from inference_gateway_tpu.otel.otel import NoopTelemetry, OpenTelemetry

_PROM_NAME = re.compile(r"^[a-zA-Z_][a-zA-Z0-9_]*$")
# Suffixes the histogram exposition appends; a counter/gauge ending in
# one would collide with some histogram's series.
_RESERVED_SUFFIXES = ("_sum", "_count", "_bucket")


def _instruments():
    return list(OpenTelemetry().registry._instruments)


def test_every_instrument_name_is_prometheus_safe_and_sanitize_idempotent():
    for inst in _instruments():
        pname = _sanitize_name(inst.name)
        assert _PROM_NAME.match(pname), f"{inst.name!r} sanitizes to invalid {pname!r}"
        assert _sanitize_name(pname) == pname, f"_sanitize_name not idempotent on {inst.name!r}"


def test_no_duplicate_instrument_registrations():
    names = [inst.name for inst in _instruments()]
    assert len(names) == len(set(names)), (
        f"duplicate registrations: {[n for n in names if names.count(n) > 1]}")
    # Sanitized names must stay distinct too — two metrics may not merge
    # in the exposition even if their raw names differ.
    sanitized = [_sanitize_name(n) for n in names]
    assert len(sanitized) == len(set(sanitized))


def test_label_names_are_exposition_safe():
    for inst in _instruments():
        for label in inst.label_names:
            assert _PROM_NAME.match(label), f"{inst.name}: bad label {label!r}"
            assert _sanitize_name(label) == label, (
                f"{inst.name}: label {label!r} changes under sanitization")
            assert not label.startswith("__"), (
                f"{inst.name}: label {label!r} uses the reserved __ prefix")


def test_histogram_boundaries_strictly_increasing_and_positive():
    for inst in _instruments():
        if not isinstance(inst, Histogram):
            continue
        bounds = inst.boundaries
        assert bounds, f"{inst.name}: histogram without boundaries"
        assert all(b > 0 for b in bounds), f"{inst.name}: non-positive boundary"
        assert all(a < b for a, b in zip(bounds, bounds[1:])), (
            f"{inst.name}: boundaries not strictly increasing: {bounds}")


def test_unit_suffix_conventions():
    for inst in _instruments():
        pname = _sanitize_name(inst.name)
        if isinstance(inst, (Counter, Gauge)):
            assert not pname.endswith(_RESERVED_SUFFIXES), (
                f"{inst.name}: name collides with histogram exposition suffixes")
        if isinstance(inst, Histogram) and inst.unit == "s":
            assert any(tok in pname for tok in ("duration", "time", "lag", "latency", "wait")), (
                f"{inst.name}: seconds histogram should name a duration/time/lag")
        if isinstance(inst, Counter):
            # Counters count discrete events ({...} annotation units) —
            # except byte totals, which carry OTel's "By" and must say
            # so in the name (ISSUE 19: engine.transfer_bytes).
            if inst.unit == "By":
                assert pname.endswith("_bytes"), (
                    f"{inst.name}: 'By' counter must be named *_bytes")
            else:
                assert inst.unit.startswith("{") or inst.unit == "", (
                    f"{inst.name}: counters count discrete events; unit {inst.unit!r}")


def test_noop_telemetry_overrides_every_recorder():
    """Drift guard: every public record_*/set_*/remove_* method on
    OpenTelemetry must be explicitly overridden by NoopTelemetry, or
    telemetry-off deployments silently pay for (and expose) it."""
    recorders = [
        name for name, val in vars(OpenTelemetry).items()
        if callable(val) and name.startswith(("record_", "set_", "remove_"))
    ]
    assert len(recorders) >= 20, f"recorder scan looks broken: {recorders}"
    missing = [n for n in recorders if n not in vars(NoopTelemetry)]
    assert not missing, (
        f"NoopTelemetry does not override {missing}; a noop gateway would "
        "run the real recorder (allocating label sets) for these")


def test_noop_recorders_record_nothing():
    noop = NoopTelemetry()
    noop.record_token_usage("s", "t", "p", "m", 10, 10)
    noop.record_request_duration("s", "t", "p", "m", "", 1.0)
    noop.record_eventloop_lag("s", 1.0)
    noop.record_eventloop_stall("s")
    noop.record_engine_step("m", "decode", 0.001)
    noop.record_host_gap("m", "decode", 0.05)
    noop.record_slow_request("s", "total")
    noop.set_engine_gauges("m", slot_occupancy=1.0)
    noop.record_wasted_tokens("m", "spec_rejected", 5)
    assert noop.token_usage.total_count() == 0
    assert noop.eventloop_lag.total_count() == 0
    assert noop.engine_step_duration.total_count() == 0
    assert noop.engine_host_gap.total_count() == 0
    assert sum(noop.slow_request_counter.values().values()) == 0
    assert noop.engine_slot_occupancy_gauge.values() == {}
    assert noop.wasted_tokens_counter.values() == {}


def test_fault_tolerance_instruments_registered_with_expected_shapes():
    """ISSUE 7: the serving-path fault-tolerance surface must expose
    exactly the advertised names — the acceptance criteria and
    dashboards key on them."""
    otel = OpenTelemetry()
    by_name = {inst.name: inst for inst in otel.registry._instruments}
    preempt = by_name["engine.preemptions"]
    assert isinstance(preempt, Counter)
    assert preempt.label_names == ("gen_ai_request_model", "reason")
    assert preempt.unit == "{preemption}"
    restarts = by_name["engine.restarts"]
    assert isinstance(restarts, Counter)
    assert restarts.label_names == ("gen_ai_request_model", "reason")
    assert restarts.unit == "{restart}"
    recovered = by_name["inference_gateway.streams_recovered"]
    assert isinstance(recovered, Counter)
    # phase distinguishes a pre-first-byte re-issue from a
    # post-first-byte continuation splice (ISSUE 9).
    assert recovered.label_names == ("alias", "from_provider", "to_provider", "phase")
    assert recovered.unit == "{stream}"
    degraded = by_name["engine.degraded"]
    assert isinstance(degraded, Gauge)
    assert degraded.label_names == ("gen_ai_request_model",)


def test_host_gap_instrument_registered_with_expected_shape():
    """ISSUE 14: the host-free-steady-state measure — the histogram name,
    labels, ms unit, and sub-ms boundary coverage are what the
    acceptance criteria and the bench artifact key on."""
    from inference_gateway_tpu.otel.metrics import Histogram

    otel = OpenTelemetry()
    by_name = {inst.name: inst for inst in otel.registry._instruments}
    hist = by_name["engine.host_gap_ms"]
    assert isinstance(hist, Histogram)
    assert hist.label_names == ("gen_ai_request_model", "kind")
    assert hist.unit == "ms"
    # A host-free dispatch is tens of µs of Python: the histogram must
    # resolve well below 1 ms or the whole measure saturates bucket 0.
    assert hist.boundaries[0] <= 0.05 and any(b == 1.0 for b in hist.boundaries)
    otel.record_host_gap("m", "decode", 0.2)
    assert hist.total_count() == 1


def test_attention_path_instrument_registered_with_expected_shape():
    """ISSUE 12: the dispatch-verdict gauge — a silently-degraded gather
    deployment must be an alertable series, and set_attention_path must
    write an explicit 0 for every inactive path (absent ≠ healthy)."""
    otel = OpenTelemetry()
    by_name = {inst.name: inst for inst in otel.registry._instruments}
    gauge = by_name["engine.attention_path"]
    assert isinstance(gauge, Gauge)
    assert gauge.label_names == ("gen_ai_request_model", "path")
    otel.set_attention_path("m", "kernel")
    vals = gauge.values()
    assert vals[("m", "kernel")] == 1
    for p in ("kernel_sharded", "kernel_replicated", "gather", "dense"):
        assert vals[("m", p)] == 0
    otel.remove_engine_gauges("m")
    assert not gauge.values()


def test_probe_instruments_registered_with_expected_shapes():
    """ISSUE 9: the active-probing surface must expose exactly the
    advertised names — the e2e acceptance and dashboards key on them."""
    otel = OpenTelemetry()
    by_name = {inst.name: inst for inst in otel.registry._instruments}
    healthy = by_name["inference_gateway.pool_healthy"]
    assert isinstance(healthy, Gauge)
    assert healthy.label_names == ("gen_ai_provider_name", "gen_ai_request_model")
    ejections = by_name["inference_gateway.probe_ejections"]
    assert isinstance(ejections, Counter)
    assert ejections.label_names == ("gen_ai_provider_name", "gen_ai_request_model")
    assert ejections.unit == "{ejection}"
    readmissions = by_name["inference_gateway.probe_readmissions"]
    assert isinstance(readmissions, Counter)
    assert readmissions.label_names == ("gen_ai_provider_name", "gen_ai_request_model")
    assert readmissions.unit == "{readmission}"


def test_noop_probe_recorders_record_nothing():
    """NoopTelemetry drift guard for the ISSUE 9 recorders."""
    noop = NoopTelemetry()
    noop.set_pool_healthy("tpu", "m", 1)
    noop.record_probe_ejection("tpu", "m")
    noop.record_probe_readmission("tpu", "m")
    noop.record_stream_recovered("alias", "a", "b", "post_first_byte")
    assert noop.pool_healthy_gauge.values() == {}
    assert noop.probe_ejection_counter.values() == {}
    assert noop.probe_readmission_counter.values() == {}
    assert noop.streams_recovered_counter.values() == {}


def test_noop_fault_tolerance_recorders_record_nothing():
    """NoopTelemetry drift guard for the ISSUE 7 recorders (the generic
    override scan catches missing methods; this pins the behavior)."""
    noop = NoopTelemetry()
    noop.record_preemption("m", "kv_pressure")
    noop.record_engine_restart("m", "step_deadline_exceeded")
    noop.record_stream_recovered("alias", "a", "b")
    noop.set_engine_degraded("m", 1)
    assert noop.engine_preemption_counter.values() == {}
    assert noop.engine_restart_counter.values() == {}
    assert noop.streams_recovered_counter.values() == {}
    assert noop.engine_degraded_gauge.values() == {}


def test_wasted_work_instrument_registered_with_expected_shape():
    """ISSUE 6 / ISSUE 46: the wasted-work counter is the one series left of
    the compute-efficiency surface; the analytic gauges are not registered."""
    otel = OpenTelemetry()
    by_name = {inst.name: inst for inst in otel.registry._instruments}
    for name in ("engine.mfu", "engine.goodput_mfu", "engine.hbm_bandwidth_util",
                 "engine.step_roofline_ratio"):
        assert name not in by_name
    wasted = by_name["engine.wasted_tokens"]
    assert isinstance(wasted, Counter)
    assert wasted.label_names == ("gen_ai_request_model", "reason")
    assert wasted.unit == "{token}"


def test_fleet_routing_instruments_registered_with_expected_shapes():
    """ISSUE 11: the fleet-routing surface must expose exactly the
    advertised names — the acceptance criteria and dashboards key on
    them."""
    otel = OpenTelemetry()
    by_name = {inst.name: inst for inst in otel.registry._instruments}
    hits = by_name["inference_gateway.routing.affinity_hits"]
    assert isinstance(hits, Counter)
    assert hits.label_names == ("alias",)
    assert hits.unit == "{request}"
    spills = by_name["inference_gateway.routing.affinity_spills"]
    assert isinstance(spills, Counter)
    assert spills.label_names == ("alias", "reason")
    assert spills.unit == "{request}"
    migrated = by_name["inference_gateway.streams_migrated"]
    assert isinstance(migrated, Counter)
    # reason distinguishes a planned drain from a supervised-restart
    # migration; from/to mirror streams_recovered for joinability.
    assert migrated.label_names == ("alias", "from_provider", "to_provider", "reason")
    assert migrated.unit == "{stream}"
    load = by_name["inference_gateway.routing.deployment_load"]
    assert isinstance(load, Gauge)
    assert load.label_names == ("gen_ai_provider_name", "gen_ai_request_model", "signal")
    assert load.ttl > 0  # stale reports age out of the exposition


def test_structured_instruments_registered_with_expected_shapes():
    """ISSUE 13: the structured-outputs surface must expose exactly the
    advertised names — the acceptance criteria and dashboards key on
    them."""
    otel = OpenTelemetry()
    by_name = {inst.name: inst for inst in otel.registry._instruments}
    constrained = by_name["engine.constrained_requests"]
    assert isinstance(constrained, Counter)
    assert constrained.label_names == ("gen_ai_request_model", "outcome")
    assert constrained.unit == "{request}"
    compile_h = by_name["engine.schema_compile.duration"]
    assert isinstance(compile_h, Histogram)
    assert compile_h.label_names == ("gen_ai_request_model",)
    assert compile_h.unit == "s"
    lookups = by_name["engine.mask_cache.lookups"]
    assert isinstance(lookups, Counter)
    assert lookups.label_names == ("gen_ai_request_model", "result")
    assert lookups.unit == "{lookup}"
    # A cache hit counts on the lookup counter only; a miss records the
    # compile time too.
    otel.record_schema_compile("m", 0.02, cache_hit=True)
    otel.record_schema_compile("m", 0.02, cache_hit=False)
    assert compile_h.total_count() == 1
    assert lookups.values()[("m", "hit")] == 1
    assert lookups.values()[("m", "miss")] == 1
    otel.record_constrained_request("m", "stop")
    assert constrained.values()[("m", "stop")] == 1


def test_noop_structured_recorders_record_nothing():
    """NoopTelemetry drift guard for the ISSUE 13 recorders."""
    noop = NoopTelemetry()
    noop.record_constrained_request("m", "stop")
    noop.record_schema_compile("m", 0.5, cache_hit=False)
    assert noop.constrained_requests_counter.values() == {}
    assert noop.mask_cache_counter.values() == {}
    assert noop.schema_compile_duration.total_count() == 0


def test_device_observatory_instruments_registered_with_expected_shapes():
    """ISSUE 19: the device-observatory surface must expose exactly the
    advertised names — the chained-submit invariant and the recompile
    alert key on them."""
    otel = OpenTelemetry()
    by_name = {inst.name: inst for inst in otel.registry._instruments}
    compile_h = by_name["engine.compile_duration"]
    assert isinstance(compile_h, Histogram)
    assert compile_h.label_names == ("gen_ai_request_model", "program")
    assert compile_h.unit == "s"
    recompiles = by_name["engine.recompiles"]
    assert isinstance(recompiles, Counter)
    assert recompiles.label_names == ("gen_ai_request_model", "program")
    assert recompiles.unit == "{compile}"
    transfers = by_name["engine.transfers"]
    assert isinstance(transfers, Counter)
    assert transfers.label_names == ("gen_ai_request_model", "direction", "path")
    assert transfers.unit == "{transfer}"
    tbytes = by_name["engine.transfer_bytes"]
    assert isinstance(tbytes, Counter)
    assert tbytes.label_names == ("gen_ai_request_model", "direction", "path")
    assert tbytes.unit == "By"
    for name in ("engine.hbm.live_bytes", "engine.hbm.peak_bytes"):
        gauge = by_name[name]
        assert isinstance(gauge, Gauge)
        assert gauge.label_names == ("gen_ai_request_model",)
        # Staleness discipline: live/peak age out when sampling stops;
        # the static plan gauge persists for the process lifetime.
        assert gauge.ttl > 0
    plan = by_name["engine.hbm.plan_bytes"]
    assert isinstance(plan, Gauge)
    assert plan.label_names == ("gen_ai_request_model",)
    # A warmup compile records duration only; a steady-state recompile
    # counts on engine.recompiles too.
    otel.record_compile("m", "decode_fn", 0.8, recompile=False)
    otel.record_compile("m", "decode_fn", 0.8, recompile=True)
    assert compile_h.total_count() == 2
    assert recompiles.values() == {("m", "decode_fn"): 1}
    # record_transfer(count=0) pre-seeds the invariant series at an
    # explicit scrapeable zero.
    otel.record_transfer("m", "h2d", "chain", 0, 0)
    assert transfers.values()[("m", "h2d", "chain")] == 0
    otel.record_transfer("m", "h2d", "fresh", 1, 128)
    assert transfers.values()[("m", "h2d", "fresh")] == 1
    assert tbytes.values()[("m", "h2d", "fresh")] == 128
    otel.set_hbm_bytes("m", plan=1000, live=900, peak=950)
    assert plan.values()[("m",)] == 1000
    otel.remove_hbm_gauges("m")
    assert plan.values() == {}


def test_noop_device_recorders_record_nothing():
    """NoopTelemetry drift guard for the ISSUE 19 recorders."""
    noop = NoopTelemetry()
    noop.record_compile("m", "decode_fn", 0.5, recompile=True)
    noop.record_transfer("m", "h2d", "chain", 1, 64)
    noop.set_hbm_bytes("m", plan=1, live=2, peak=3)
    noop.remove_hbm_gauges("m")
    assert noop.engine_compile_duration.total_count() == 0
    assert noop.engine_recompile_counter.values() == {}
    assert noop.engine_transfer_counter.values() == {}
    assert noop.engine_hbm_live_gauge.values() == {}
    assert noop.engine_hbm_plan_gauge.values() == {}


def test_noop_fleet_recorders_record_nothing():
    """NoopTelemetry drift guard for the ISSUE 11 recorders."""
    noop = NoopTelemetry()
    noop.record_affinity_hit("alias")
    noop.record_affinity_spill("alias", "saturated")
    noop.record_stream_migrated("alias", "a", "b", "drain")
    noop.set_deployment_load("tpu", "m", "queue_depth", 3.0)
    assert noop.affinity_hit_counter.values() == {}
    assert noop.affinity_spill_counter.values() == {}
    assert noop.streams_migrated_counter.values() == {}
    assert noop.deployment_load_gauge.values() == {}


def test_no_instrument_carries_a_trace_or_journey_label():
    """Cardinality lint (ISSUE 18): journeys are keyed by trace id —
    an UNBOUNDED value space. The journey/SLO observability plane must
    never leak that key into a metric label: a trace-labeled series is
    a memory leak and a scrape bomb. Per-request identity belongs in
    ``/debug/journey``, spans, and wide events — never the exposition."""
    banned = ("trace", "journey_id", "request_id", "span", "completion_id")
    for inst in _instruments():
        for label in inst.label_names:
            assert not any(tok in label.lower() for tok in banned), (
                f"{inst.name}: label {label!r} smells like per-request "
                "identity — unbounded cardinality in the exposition")


def test_slo_and_journey_instruments_registered_with_expected_shapes():
    """ISSUE 18: the fleet-observability surface must expose exactly
    the advertised names — the acceptance criteria key on them. Every
    label is bounded by construction: slo/window/event are fixed
    vocabularies, tenant folds into SLO_MAX_TENANT_SERIES buckets,
    pool comes from the operator's own config."""
    otel = OpenTelemetry()
    by_name = {inst.name: inst for inst in otel.registry._instruments}
    for name in ("inference_gateway.slo.burn_rate",
                 "inference_gateway.slo.error_budget_remaining"):
        inst = by_name[name]
        assert isinstance(inst, Gauge)
        assert inst.label_names == ("slo", "window", "tenant")
        assert inst.ttl > 0  # evicted tenant series age out
    for name in ("inference_gateway.slo.pool_burn_rate",
                 "inference_gateway.slo.pool_error_budget_remaining"):
        inst = by_name[name]
        assert isinstance(inst, Gauge)
        assert inst.label_names == ("slo", "window", "pool")
        assert inst.ttl > 0
    events = by_name["inference_gateway.journey.events"]
    assert isinstance(events, Counter)
    assert events.label_names == ("event",)  # bounded JOURNEY_EVENTS vocab
    assert events.unit == "{event}"
    # The tenant in-flight gauge grew a source label (worker vs cluster)
    # so the cluster-merged value is distinguishable from a single
    # worker's local view.
    tenant_gauge = by_name["inference_gateway.tenant.in_flight"]
    assert isinstance(tenant_gauge, Gauge)
    assert tenant_gauge.label_names == ("tenant", "source")
    # Wiring smoke: both sides of each pair land under the same labels.
    otel.set_slo_burn_rate("availability", "5m", "t1", 2.0, -1.0)
    assert otel.slo_burn_rate_gauge.values()[("availability", "5m", "t1")] == 2.0
    assert otel.slo_budget_gauge.values()[("availability", "5m", "t1")] == -1.0
    otel.record_journey_event("admitted")
    assert events.values()[("admitted",)] == 1


def test_journey_event_label_values_are_the_bounded_vocabulary():
    """The journey event counter's label values come from the
    JOURNEY_EVENTS tuple — the lintable bound the cardinality rule
    relies on. Every recorder call site uses a literal from it."""
    from inference_gateway_tpu.otel.journey import JOURNEY_EVENTS

    assert set(JOURNEY_EVENTS) == {
        "admitted", "shed", "routed", "first_byte", "recovered",
        "migrated", "spliced", "finished"}


def test_slo_tenant_series_are_bounded_by_overflow_folding():
    """SLO_MAX_TENANT_SERIES caps the distinct tenant label values: the
    long tail folds into stable overflow buckets, so the series count
    never exceeds max named + max overflow buckets however many tenants
    hit the gateway."""
    from inference_gateway_tpu.otel.slo import SloTracker
    from inference_gateway_tpu.resilience.clock import VirtualClock

    slo = SloTracker(max_tenant_series=8, clock=VirtualClock())
    for i in range(100):
        slo.observe(tenant=f"key:{i:04d}", ok=(i % 3 != 0))
    keys = set(slo._scopes["tenant"])
    assert len(keys) <= 16  # 8 named + at most 8 overflow buckets
    overflow = {k for k in keys if k.startswith("overflow-")}
    assert overflow, "overflow folding never engaged"
    # Folding is stable: the same tenant lands in the same bucket.
    assert slo.tenant_key("key:0099") == slo.tenant_key("key:0099")


def test_noop_slo_and_journey_recorders_record_nothing():
    """NoopTelemetry drift guard for the ISSUE 18 recorders."""
    noop = NoopTelemetry()
    noop.set_slo_burn_rate("availability", "5m", "t", 1.0, 0.0)
    noop.set_pool_slo_burn_rate("ttft", "1h", "tpu/m", 1.0, 0.0)
    noop.record_journey_event("admitted")
    noop.set_tenant_in_flight("t", 3, source="cluster")
    noop.remove_tenant_gauge("t", source="cluster")
    assert noop.slo_burn_rate_gauge.values() == {}
    assert noop.slo_budget_gauge.values() == {}
    assert noop.slo_pool_burn_rate_gauge.values() == {}
    assert noop.slo_pool_budget_gauge.values() == {}
    assert noop.journey_event_counter.values() == {}
    assert noop.tenant_in_flight_gauge.values() == {}
