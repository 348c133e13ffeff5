"""Engine device observatory (ISSUE 19): the compile/recompile ledger,
the honest HBM pane, always-on transfer auditing, and the zero-overhead
off switch.

Pins the acceptance contracts:

1. Warmup compiles land in /debug/compile with program name, static
   shape signature and compile wall-ms, and every program is lowered
   once per compile: the ledger sees a compile, it never repeats one
   (ISSUE 46).
2. A forced shape change after warmup fires EXACTLY ONE steady-state
   recompile: `engine.recompiles` increments and a wide event with the
   shape-signature diff (naming the changed static argument) is kept.
3. A chained (chain=True) decode submit reads ZERO on
   engine.transfers{direction="h2d",path="chain"} on a LIVE /metrics
   scrape — JSON and Prometheus text — while the fresh/prefill uploads
   around it are accounted.
4. /debug/hbm is honest off-TPU: measured:false with the analytic plan
   and KV-page high-water, never fabricated live/peak bytes.
5. TELEMETRY_DEVICE_ENABLE=false installs nothing — no wrappers on the
   engine, 404s on both debug panes, no device keys on /metrics.
"""

from __future__ import annotations

import json
import time

import numpy as np
import pytest

import jax

from inference_gateway_tpu.netio.client import HTTPClient
from inference_gateway_tpu.netio.server import Headers, Request
from inference_gateway_tpu.otel.device_observatory import JIT_ENTRY_POINTS
from inference_gateway_tpu.otel.otel import OpenTelemetry
from inference_gateway_tpu.serving.engine import Engine, EngineConfig
from inference_gateway_tpu.serving.scheduler import Scheduler
from inference_gateway_tpu.serving.server import SidecarServer


def _cfg(attention="paged", **kw):
    base = dict(model="test-tiny", max_slots=4, max_seq_len=128, dtype="float32",
                max_prefill_batch=2, use_mesh=False, attention=attention,
                page_size=16, prefix_cache=False, decode_chunk=4,
                prefill_buckets=(16, 32, 64))
    base.update(kw)
    return EngineConfig(**base)


def _req(path, query=None):
    return Request(method="GET", path=path, query=query or {},
                   headers=Headers(), body=b"")


def _sidecar(engine, **kw):
    return SidecarServer(engine, served_model_name="test-tiny",
                         otel=OpenTelemetry(), **kw)


# ---------------------------------------------------------------------------
# 1. Warmup ledger: programs, signatures, wall-ms
# ---------------------------------------------------------------------------
async def test_warmup_compiles_land_in_ledger():
    eng = Engine(_cfg("paged"))
    sidecar = _sidecar(eng)
    eng.warmup()

    resp = await sidecar.debug_compile(_req("/debug/compile"))
    assert resp.status == 200
    snap = json.loads(resp.body)
    assert snap["model"] == "test-tiny"
    assert snap["warmed"] is True
    assert snap["compiles"] >= 4  # decode, 2x decode_chunk shapes, prefill
    assert snap["recompiles"] == 0 and snap["recompile_events"] == []

    records = snap["records"]
    assert len(records) == snap["compiles"]
    for rec in records:
        assert rec["program"]
        assert rec["signature"]  # static shape signature, never empty
        assert rec["compile_ms"] > 0
        assert rec["recompile"] is False
    programs = {r["program"] for r in records}
    assert any(p.startswith("decode") for p in programs)
    assert any(p.startswith("prefill") for p in programs)
    assert set(records[0]) == {"program", "signature", "compile_ms", "recompile"}


# The seven tiny presets, one per family the benchmark's configurations
# come from; the paged engine each is served with.
_PRESETS = {
    "test-tiny": {},
    "qwen2-test-tiny": {},
    "smallthinker-test-tiny": {},
    "phi4flash-test-tiny": {},
    "keye-test-tiny": {"page_size": 8},
    "trinity-test-tiny": {"page_size": 8},
    "lfm2-test-tiny": {"page_size": 8},
}


@pytest.mark.parametrize("preset", list(_PRESETS))
def test_warmup_lowers_every_program_once(preset):
    """With the observatory attached, warm-up lowers each program once:
    the ledger's wrapper sees that a call compiled and leaves the program
    alone. The parent went back to it after every compile
    (``fn.lower(...)`` again, for the compiler's FLOP count): on this JAX
    that second pass was answered from the trace and lowering caches and
    paid only the HLO conversion and the cost pass, so the parent reads
    the same count here; what is held is that no observer builds a
    program's StableHLO a second time, Pallas kernels and all, inside
    every start. Counted where JAX itself reports a lowering,
    ``jaxpr_to_mlir_module`` duration events: a trace counter would not
    see a second pass, which hits the trace cache."""
    from jax import monitoring

    from inference_gateway_tpu.otel.device_observatory import DeviceObservatory

    eng = Engine(_cfg("paged", model=preset, **_PRESETS[preset]))
    obs = DeviceObservatory(model=preset)
    obs.attach(eng)
    programs = {f"jit({name})" for name in JIT_ENTRY_POINTS
                if hasattr(getattr(eng, name, None), "_ledger_inner")}
    lowered = []

    def on_event(event, duration, **kw):
        # Only the engine's own entry points: warm-up also runs small
        # host-side helpers (jnp ops outside any wrapped program).
        if event.endswith("jaxpr_to_mlir_module_duration") and kw.get("fun_name") in programs:
            lowered.append(kw["fun_name"])

    monitoring.register_event_duration_secs_listener(on_event)
    try:
        eng.warmup()
    finally:
        monitoring.unregister_event_duration_listener(on_event)
    snap = obs.ledger.snapshot()
    assert snap["compiles"] >= 2 and snap["recompiles"] == 0
    assert len(lowered) == snap["compiles"], (sorted(lowered), [r["program"] for r in snap["records"]])


# ---------------------------------------------------------------------------
# 2. Steady-state recompile: exactly one event, with the signature diff
# ---------------------------------------------------------------------------
async def test_forced_shape_change_after_warmup_fires_exactly_one_recompile():
    eng = Engine(_cfg("paged"))
    sidecar = _sidecar(eng)
    eng.warmup()  # compiles decode_chunk at n_steps=4 and n_steps=1
    assert sidecar.observatory.ledger.recompile_count() == 0

    # A decode chunk with a NEVER-WARMED static n_steps is the classic
    # silent-latency-cliff bug this pane exists to catch.
    S = eng.config.max_slots
    args = (np.zeros((S,), np.int32), np.zeros((S,), np.int32),
            np.zeros((S,), bool), np.zeros((S,), np.float32),
            np.ones((S,), np.float32))
    eng.decode_chunk(*args, n_steps=3)

    snap = json.loads((await sidecar.debug_compile(_req("/debug/compile"))).body)
    assert snap["recompiles"] == 1
    assert len(snap["recompile_events"]) == 1
    ev = snap["recompile_events"][0]
    assert "decode_chunk" in ev["program"]
    assert ev["prev_signature"] and ev["signature"] != ev["prev_signature"]
    # The diff names the changed static argument — pinned: the operator
    # must see WHICH shape moved, not just that one did.
    assert ev["diff"], "recompile event has no signature diff"
    assert any("n_steps=3" in d for d in ev["diff"]), ev["diff"]
    assert ev["compile_ms"] > 0
    # The otel counter moved once, labeled with the program.
    recompiled = {labels: v for labels, v
                  in sidecar.otel.engine_recompile_counter.values().items()}
    assert sum(recompiled.values()) == 1
    assert any("decode_chunk" in labels[1] for labels in recompiled)

    # Replaying the SAME shape hits the cache: no second event.
    eng.decode_chunk(*args, n_steps=3)
    assert sidecar.observatory.ledger.recompile_count() == 1
    assert json.loads((await sidecar.metrics(_req("/metrics"))).body)["recompiles"] == 1


def test_scheduler_attributes_recompile_stall_to_the_step_that_paid_it():
    """The ledger delta since the scheduler's last record rides that
    step's timeline row as cost["recompiled"] — the p99 spike and its
    cause land together."""
    class _FakeLedger:
        n = 0
        def recompile_count(self):
            return self.n

    class _FakeObs:
        ledger = _FakeLedger()

    class _Capture:
        def __init__(self):
            self.rows = []
        def record(self, kind, duration, **kw):
            self.rows.append((kind, kw))

    eng = Engine(_cfg("dense"))
    s = Scheduler(eng)
    s.timeline = _Capture()
    s.observatory = _FakeObs()
    s._record_step("decode", time.perf_counter(), n_steps=1, batch=1, tokens=1)
    assert s.timeline.rows[0][1]["cost"] is None  # no recompile, no noise
    s.observatory.ledger.n = 2
    s._record_step("decode", time.perf_counter(), n_steps=1, batch=1, tokens=1)
    assert s.timeline.rows[1][1]["cost"]["recompiled"] == 2
    # Delta consumed: the next quiet step does not re-report it.
    s._record_step("decode", time.perf_counter(), n_steps=1, batch=1, tokens=1)
    assert s.timeline.rows[2][1]["cost"] is None


# ---------------------------------------------------------------------------
# 3. Transfer audit: chained submits read zero h2d on a LIVE scrape
# ---------------------------------------------------------------------------
async def test_chained_submits_read_zero_h2d_on_live_metrics_scrape():
    eng = Engine(_cfg("paged", max_seq_len=256,
                      pipeline_depth=6))
    sidecar = _sidecar(eng)
    port = await sidecar.start("127.0.0.1", 0)
    try:
        # Establish the chain (test_desync_decode idiom): prefill, one
        # fresh submit, then host-free chained submits under the
        # transfer guard — the audit must agree with the guard.
        res = eng.prefill([[1, 2, 3, 4]], [0], [0.0], [1.0])[0]
        S = eng.config.max_slots
        tokens = np.zeros((S,), np.int32)
        positions = np.zeros((S,), np.int32)
        active = np.zeros((S,), bool)
        temps = np.zeros((S,), np.float32)
        top_ps = np.ones((S,), np.float32)
        tokens[0], positions[0], active[0] = res.first_token, 4, True
        eng.decode_chunk_fetch(
            eng.decode_chunk_submit(tokens, positions, active, temps, top_ps))
        with jax.transfer_guard_host_to_device("disallow"):
            handles = [eng.decode_chunk_submit(None, None, None, None, None,
                                               chain=True) for _ in range(2)]
        for h in handles:
            eng.decode_chunk_fetch(h)

        client = HTTPClient()
        m = (await client.get(f"http://127.0.0.1:{port}/metrics")).json()
        transfers = m["transfers"]
        # THE invariant: the series exists (seeded, scrapeable, usable
        # in the PromQL alert) and reads exactly zero.
        assert transfers["h2d/chain"]["count"] == 0
        assert transfers["h2d/chain"]["bytes"] == 0
        assert m["h2d_chain_transfers"] == 0
        # ...while the uploads that legitimately happened are accounted.
        assert transfers["h2d/prefill"]["count"] >= 1
        assert transfers["h2d/fresh"]["count"] >= 1
        # ISSUE 25: a fresh submit stages ONE array, its packed buffer.
        assert transfers["h2d/fresh"]["arrays"] == transfers["h2d/fresh"]["count"]
        assert transfers["h2d/chain"]["arrays"] == 0
        assert transfers["d2h/chunk"]["count"] >= 3  # fresh + 2 chained fetches
        assert all(slot["bytes"] > 0 for key, slot in transfers.items()
                   if slot["count"] > 0)

        prom = (await client.get(
            f"http://127.0.0.1:{port}/metrics?format=prometheus")).body.decode()
        assert "tpu_sidecar_transfers_h2d_chain 0" in prom
        assert "tpu_sidecar_transfers_h2d_fresh" in prom
    finally:
        await sidecar.shutdown()


async def test_packed_submits_stage_one_array_with_its_true_bytes():
    """ISSUE 25: `device.transfers` of /debug/status reads arrays / count
    <= 2 (here 1) for `h2d/mixed` and `h2d/fresh`, `bytes` is the packed
    buffers' nbytes, and `h2d/chain` stays 0 under the transfer guard."""
    from inference_gateway_tpu.serving.engine import MixedRow

    eng = Engine(_cfg("paged", max_seq_len=256, mixed_step=True))
    sidecar = _sidecar(eng)
    port = await sidecar.start("127.0.0.1", 0)
    try:
        toks = None
        for start in (0, 5, 10):  # three mixed steps: a prompt in chunks of five
            toks, _ = eng.mixed_step_fetch(eng.mixed_step_submit([MixedRow(
                slot=0, token_ids=[9, 8, 7, 6, 5], start=start, kind="prefill")]))
        S = eng.config.max_slots
        tokens = np.zeros((S,), np.int32)
        positions = np.zeros((S,), np.int32)
        active = np.zeros((S,), bool)
        tokens[0], positions[0], active[0] = toks[0], 15, True
        idle = (np.zeros((S,), np.float32), np.ones((S,), np.float32))
        for _ in range(2):  # two fresh submits from the same host state
            eng.decode_chunk_fetch(eng.decode_chunk_submit(tokens, positions, active, *idle))
        with jax.transfer_guard_host_to_device("disallow"):
            handle = eng.decode_chunk_submit(None, None, None, None, None, chain=True)
        eng.decode_chunk_fetch(handle)
        assert handle.h2d_arrays == 0

        client = HTTPClient()
        status = (await client.get(f"http://127.0.0.1:{port}/debug/status")).json()
        transfers = status["device"]["transfers"]
        for path, n, pack in (("h2d/mixed", 3, eng._mixed_pack), ("h2d/fresh", 2, eng._fresh_pack)):
            assert transfers[path]["count"] == n
            assert transfers[path]["arrays"] / transfers[path]["count"] <= 2
            assert transfers[path]["arrays"] == n
            assert transfers[path]["bytes"] == n * pack.snapshot().nbytes == n * 4 * pack.size
        assert transfers["h2d/chain"] == {"count": 0, "arrays": 0, "bytes": 0}
    finally:
        await sidecar.shutdown()


# ---------------------------------------------------------------------------
# 4. HBM pane: honest off-TPU
# ---------------------------------------------------------------------------
async def test_hbm_pane_reports_plan_and_never_fabricates_live_bytes():
    eng = Engine(_cfg("paged"))
    sidecar = _sidecar(eng)
    eng.prefill([[1, 2, 3, 4, 5]], [0], [0.0], [1.0])

    resp = await sidecar.debug_hbm(_req("/debug/hbm"))
    assert resp.status == 200
    snap = json.loads(resp.body)
    plan = snap["plan"]
    assert plan["weights_bytes"] > 0 and plan["kv_pool_bytes"] > 0
    # The logit-bias table is in the plan from the engine's construction
    # (ISSUE 49): every step program reads it, biased request or not.
    assert plan["logit_bias_bytes"] == eng.structured.bias_dev.nbytes
    assert plan["plan_bytes"] == (plan["weights_bytes"] + plan["kv_pool_bytes"]
                                  + plan["logit_bias_bytes"])
    pages = snap["kv_pages"]
    assert pages["total"] == eng.allocator.num_pages
    assert 1 <= pages["high_water"] <= pages["total"]
    assert pages["high_water_bytes"] > 0
    if not snap["measured"]:
        # CPU host: the pane says so instead of inventing numbers.
        assert "note" in snap
        assert "live_bytes" not in snap and "peak_bytes" not in snap
    else:  # a real device backend: live/peak come from memory_stats()
        assert snap["live_bytes"] > 0 and snap["peak_bytes"] >= snap["live_bytes"]

    # The OTLP push payload mirrors the honesty: plan_bytes always,
    # live/peak only when measured.
    names = {m["name"] for m in sidecar._otlp_payload()
             ["resourceMetrics"][0]["scopeMetrics"][0]["metrics"]}
    assert "engine.hbm.plan_bytes" in names
    if not snap["measured"]:
        assert "engine.hbm.live_bytes" not in names

    # /debug/status carries all three panes for the fleet view.
    status = json.loads((await sidecar.debug_status(_req("/debug/status"))).body)
    assert set(status["device"]) == {"compile", "transfers", "hbm"}
    # ... and the engine's mesh as axis -> size (ISSUE 28): the byte-counting
    # readers of the benchmark take the layout from here, never assume it.
    assert status["mesh"] == sidecar.engine.mesh_axes and all(
        isinstance(v, int) and v >= 1 for v in status["mesh"].values()) and "tp" in status["mesh"]
    brief = json.loads((await sidecar.debug_status(
        _req("/debug/status", {"brief": ["1"]}))).body)
    assert {"compiles", "recompiles", "h2d_chain", "hbm_measured",
            "hbm_live_bytes"} <= set(brief["device"])


# ---------------------------------------------------------------------------
# 5. The off switch: zero instrumentation installed
# ---------------------------------------------------------------------------
async def test_device_disable_installs_no_wrappers_and_404s_debug_panes():
    eng = Engine(_cfg("paged"))
    sidecar = _sidecar(eng, device_enable=False)
    assert sidecar.observatory is None
    assert sidecar.scheduler.observatory is None
    assert getattr(eng, "observatory", None) is None
    # No instance-attribute shadows: every jit entry point is still the
    # pristine class attribute — literally zero per-call overhead.
    assert all(name not in eng.__dict__ for name in JIT_ENTRY_POINTS)
    assert all(getattr(getattr(eng, name, None), "_ledger_inner", None) is None
               for name in JIT_ENTRY_POINTS)

    for handler in (sidecar.debug_compile, sidecar.debug_hbm):
        resp = await handler(_req("/debug/x"))
        assert resp.status == 404
        assert "TELEMETRY_DEVICE_ENABLE" in json.loads(resp.body)["error"]

    m = json.loads((await sidecar.metrics(_req("/metrics"))).body)
    assert "compiles" not in m and "transfers" not in m
    status = json.loads((await sidecar.debug_status(_req("/debug/status"))).body)
    assert "device" not in status


async def test_attach_is_idempotent_and_wrappers_single_layer():
    eng = Engine(_cfg("dense"))
    sidecar = _sidecar(eng)
    obs = sidecar.observatory
    obs.attach(eng)  # restart path re-attaches; must not double-wrap
    for name in JIT_ENTRY_POINTS:
        fn = getattr(eng, name, None)
        if fn is None or not hasattr(fn, "_ledger_inner"):
            continue
        assert getattr(fn._ledger_inner, "_ledger_inner", None) is None, name


# ---------------------------------------------------------------------------
# 8. HBM pane across devices: a sharded engine is read on every device
# ---------------------------------------------------------------------------
def test_hbm_snapshot_sums_and_lists_every_device(monkeypatch):
    import jax

    from inference_gateway_tpu.otel.device_observatory import DeviceObservatory

    class _Dev:
        def __init__(self, i, live):
            self.id, self._live = i, live

        def memory_stats(self):
            return {"bytes_in_use": self._live, "peak_bytes_in_use": self._live + 10}

    monkeypatch.setattr(jax, "devices", lambda: [_Dev(i, 100 + i) for i in range(4)])
    obs = DeviceObservatory(model="m")
    obs._plan = {"plan_bytes": 400}
    snap = obs.hbm_snapshot()
    assert snap["measured"] is True
    assert [d["id"] for d in snap["per_device"]] == [0, 1, 2, 3]
    assert snap["live_bytes"] == 100 + 101 + 102 + 103
    assert snap["peak_bytes"] == snap["live_bytes"] + 40
    assert snap["live_vs_plan"] == round(snap["live_bytes"] / 400, 4)
