"""Multi-worker gateway cluster (ISSUE 16).

Four layers, matching the tentpole:

- ``ClusterSegment``/``WorkerSlab`` unit behavior: layout validation on
  attach, generation epochs, live-slab merges, seqlock blobs, the
  peer-ejection quorum, the Prometheus/status merge surfaces.
- Tenant derivation and the admission ledger's quota/fairness policy on
  a VirtualClock (zero real sleeps): the noisy-tenant acceptance — a
  10×-weight tenant offering 2× the class cap sheds against ITSELF
  while a quiet tenant is never shed below its fair share — plus
  cluster-wide quota occupancy through the shared segment and the
  kill-switch posture.
- The supervisor against real scripted worker processes: exit-code
  death and wedged-heartbeat staleness both reap + respawn under a
  fresh generation, and a SIGKILLed worker's admission tickets, quota
  holds, and tenant gauge series are reclaimed by the generation reap
  (the ticket-leak regression).
- The full real-process e2e: a supervisor forking two REAL gateway
  workers onto one SO_REUSEPORT port in front of a real TPU sidecar —
  SIGKILLing one worker drops zero non-streamed requests, and a
  mid-SSE-stream SIGKILL completes byte-identically through the PR 9
  continuation splice under one trace id with once-only billing.
"""

import asyncio
import json
import os
import signal
import socket
import subprocess
import sys
import textwrap
import time
import uuid
from pathlib import Path

import pytest

from inference_gateway_tpu.cluster.shm import (
    GATEWAY_COUNTERS,
    ClusterSegment,
    PeerHealthView,
    tenant_slot,
)
from inference_gateway_tpu.cluster.supervisor import Supervisor, gateway_spawn
from inference_gateway_tpu.cluster.tenancy import TenantPolicy, derive_tenant
from inference_gateway_tpu.config import OverloadConfig, TenantConfig
from inference_gateway_tpu.netio.client import HTTPClient, HTTPClientError
from inference_gateway_tpu.netio.server import Headers
from inference_gateway_tpu.otel.access_log import AccessLog
from inference_gateway_tpu.resilience import (
    CLASS_STREAMING,
    PRIORITY_INTERACTIVE,
    AdmissionRejectedError,
    OverloadController,
    VirtualClock,
)
from inference_gateway_tpu.resilience.faults import EngineFaultInjector
from inference_gateway_tpu.serving.engine import Engine, EngineConfig
from inference_gateway_tpu.serving.server import SidecarServer

REPO_ROOT = Path(__file__).resolve().parent.parent
TRACEPARENT = "00-abcdefabcdefabcdefabcdefabcdef34-1234567890abcdef-01"


def _name() -> str:
    return f"ig-test-{os.getpid()}-{uuid.uuid4().hex[:6]}"


def _wait(pred, timeout: float = 90.0, interval: float = 0.05) -> bool:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return False


async def _await(pred, timeout: float = 90.0, interval: float = 0.05) -> bool:
    """Async twin of ``_wait`` for the e2e tests: they share ONE event
    loop with the supervisor's monitor task, so blocking in time.sleep
    would also stop the reaper whose effect they are waiting for."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        await asyncio.sleep(interval)
    return False


# ---------------------------------------------------------------------------
# Shared-memory segment
# ---------------------------------------------------------------------------
def test_segment_create_attach_merge_roundtrip():
    name = _name()
    seg = ClusterSegment.create(name, workers=2)
    try:
        seg.begin_generation(0, 1)
        seg.begin_generation(1, 2)
        seg.slab(0).add("in_flight_streaming", 2)
        seg.slab(1).add("in_flight_streaming", 3)
        seg.slab(1).add("shed_total", 1)
        other = ClusterSegment.attach(name, workers=2)
        try:
            assert other.totals()["in_flight_streaming"] == 5
            assert other.counter_total("shed_total") == 1
            assert other.worker_counter(0, "in_flight_streaming") == 2
            assert other.live() == [0, 1]
        finally:
            other.close()
    finally:
        seg.close(unlink=True)


def test_attach_rejects_layout_mismatch():
    name = _name()
    seg = ClusterSegment.create(name, workers=2)
    try:
        with pytest.raises(ValueError):
            ClusterSegment.attach(name, workers=3)
        with pytest.raises(ValueError):
            ClusterSegment.attach(name, workers=2, counters=("held",))
    finally:
        seg.close(unlink=True)


def test_dead_slot_is_excluded_and_reap_reclaims():
    name = _name()
    seg = ClusterSegment.create(name, workers=2)
    try:
        seg.begin_generation(0, 1)
        seg.begin_generation(1, 2)
        seg.slab(0).add("in_flight_buffered", 4)
        seg.slab(0).tenant_add(5, 2)
        reclaimed = seg.reap(0)
        assert reclaimed["in_flight_buffered"] == 4
        assert seg.generation(0) == 0
        assert seg.live() == [1]
        # Dead slab contributes nothing to any merge surface.
        assert seg.totals().get("in_flight_buffered", 0) == 0
        assert seg.tenant_totals() == {}
        # A fresh generation starts from zero.
        seg.begin_generation(0, 3)
        assert seg.worker_counter(0, "in_flight_buffered") == 0
        assert seg.slab(0).generation == 3
    finally:
        seg.close(unlink=True)


def test_blob_seqlock_roundtrip():
    name = _name()
    seg = ClusterSegment.create(name, workers=1)
    try:
        seg.begin_generation(0, 1)
        assert seg.read_blob(0) is None
        seg.slab(0).publish({"pid": 42, "probes": {"tpu/m": True}})
        assert seg.read_blob(0) == {"pid": 42, "probes": {"tpu/m": True}}
        seg.slab(0).publish({"pid": 42, "probes": {}})
        assert seg.blobs() == {0: {"pid": 42, "probes": {}}}
    finally:
        seg.close(unlink=True)


def test_peer_ejected_quorum_only_removes_candidates():
    name = _name()
    seg = ClusterSegment.create(name, workers=4)
    try:
        for i in range(4):
            seg.begin_generation(i, i + 1)
        # Only one peer has an opinion and it says ejected -> ejected
        # (ties eject: the merge is deliberately pessimistic — it can
        # only REMOVE candidates, never readmit them).
        seg.slab(1).publish({"probes": {"tpu/m": True}})
        assert seg.peer_ejected(0, "tpu", "m") is True
        # One eject vs one healthy is still "at least half" -> ejected.
        seg.slab(2).publish({"probes": {"tpu/m": False}})
        assert seg.peer_ejected(0, "tpu", "m") is True
        # Healthy peers outvoting the one confused worker -> admitted.
        seg.slab(3).publish({"probes": {"tpu/m": False}})
        assert seg.peer_ejected(0, "tpu", "m") is False
        # Own slab's opinion is excluded from the peer vote.
        seg.slab(0).publish({"probes": {"tpu/other": True}})
        assert seg.peer_ejected(0, "tpu", "other") is False
        # No votes at all -> no peer ejection.
        assert seg.peer_ejected(0, "tpu", "missing") is False
    finally:
        seg.close(unlink=True)


def test_peer_health_view_is_a_refreshed_cache():
    """The routing hot path reads peer verdicts through PeerHealthView:
    a set lookup against the last refresh() — blob decodes happen only
    on the heartbeat-interval refresh, and the merged answer matches
    the one-shot peer_ejected() quorum exactly."""
    name = _name()
    seg = ClusterSegment.create(name, workers=3)
    try:
        for i in range(3):
            seg.begin_generation(i, i + 1)
        view = PeerHealthView(seg, 0)
        # Before any refresh the view is empty (nothing ejected).
        assert view.ejected("tpu", "m") is False
        seg.slab(1).publish({"probes": {"tpu/m": True, "tpu/ok": False}})
        # Published but not yet refreshed: the cache still answers old.
        assert view.ejected("tpu", "m") is False
        view.refresh()
        assert view.ejected("tpu", "m") is True
        assert view.ejected("tpu", "ok") is False
        assert view.ejected("tpu", "missing") is False
        # Quorum flip: a healthy outvote readmits on the next refresh.
        seg.slab(2).publish({"probes": {"tpu/m": False}})
        seg.slab(0).publish({"probes": {"tpu/m": False}})  # own vote ignored
        assert view.ejected("tpu", "m") is True  # cached until refresh
        view.refresh()
        # 1 eject vs 1 healthy peer is still "at least half" -> ejected;
        # matches the one-shot merge bit for bit.
        assert view.ejected("tpu", "m") == seg.peer_ejected(0, "tpu", "m")
        # A reaped peer's votes vanish from the next refresh.
        seg.reap(1)
        view.refresh()
        assert view.ejected("tpu", "m") is False
    finally:
        seg.close(unlink=True)


def test_render_prometheus_and_status_merge():
    name = _name()
    seg = ClusterSegment.create(name, workers=2)
    try:
        seg.begin_generation(0, 1, pid=111, now=10.0)
        seg.slab(0).add("admitted_total", 7)
        seg.slab(0).tenant_add(3, 2)
        text = seg.render_prometheus(now=10.5)
        assert 'cluster_worker_up{worker="0"} 1' in text
        assert 'cluster_worker_up{worker="1"} 0' in text
        assert 'cluster_admission{counter="admitted_total"} 7' in text
        assert 'cluster_tenant_in_flight{slot="3"} 2' in text
        status = seg.status(now=10.5)
        assert status["live"] == [0]
        assert status["totals"]["admitted_total"] == 7
        assert status["per_worker"][0]["pid"] == 111
        assert status["per_worker"][1] == {"worker": 1, "generation": 0}
    finally:
        seg.close(unlink=True)


def test_tenant_slot_is_stable_and_bounded():
    assert tenant_slot("key:abc123", 64) == tenant_slot("key:abc123", 64)
    assert 0 <= tenant_slot("anything", 8) < 8
    assert tenant_slot("a", 64) != tenant_slot("b", 64) or True  # collisions legal


# ---------------------------------------------------------------------------
# Tenant derivation + policy
# ---------------------------------------------------------------------------
def _headers(**kw) -> Headers:
    h = Headers()
    for k, v in kw.items():
        h.set(k.replace("_", "-"), v)
    return h


def _jwt(sub: str) -> str:
    import base64

    def b64(obj) -> str:
        raw = json.dumps(obj).encode()
        return base64.urlsafe_b64encode(raw).rstrip(b"=").decode()

    return f"{b64({'alg': 'none'})}.{b64({'sub': sub})}.sig"


def test_derive_tenant_sources():
    policy = TenantPolicy(TenantConfig(enabled=True))
    # API key wins; the id is a stable digest, never the raw secret.
    t = derive_tenant(_headers(x_api_key="sk-secret-1"), policy)
    assert t.startswith("key:") and "secret" not in t
    assert t == derive_tenant(_headers(x_api_key="sk-secret-1"), policy)
    assert t != derive_tenant(_headers(x_api_key="sk-secret-2"), policy)
    # An UNVERIFIED bearer JWT buckets by token digest, NOT its claims
    # — a forged sub must never pick a victim's fairness bucket.
    unverified = derive_tenant(
        _headers(authorization=f"Bearer {_jwt('team-a')}"), policy)
    assert unverified.startswith("key:")
    # Opaque bearer tokens hash like keys.
    opaque = derive_tenant(_headers(authorization="Bearer not.a.jwt!"), policy)
    assert opaque.startswith("key:")
    # Nothing at all -> the configured anonymous bucket.
    assert derive_tenant(_headers(), policy) == "anonymous"


def test_verified_bearer_maps_to_subject_forged_sub_cannot():
    """The targeted-impersonation regression: only a token the auth
    middleware has VERIFIED maps to its sub bucket. A forged token
    carrying the same sub stays in its own digest bucket, so pre-auth
    garbage can never drive load into a victim tenant's quota."""
    policy = TenantPolicy(TenantConfig(enabled=True))
    real = _jwt("team-a")
    headers = _headers(authorization=f"Bearer {real}")
    before = derive_tenant(headers, policy)
    assert before.startswith("key:")
    # The auth middleware verified the signature -> sub bucket sticks.
    policy.record_verified(real, "team-a")
    assert derive_tenant(headers, policy) == "sub:team-a"
    # A DIFFERENT token forging the same sub is not the verified token:
    # it buckets by its own digest, isolated from team-a's budget.
    forged = _jwt("team-a") + "forged"
    got = derive_tenant(_headers(authorization=f"Bearer {forged}"), policy)
    assert got.startswith("key:") and got != "sub:team-a"
    # Hostile verified subjects are sanitized into the label charset.
    hostile = _jwt("a b\nc{evil}")
    policy.record_verified(hostile, "a b\nc{evil}")
    weird = derive_tenant(_headers(authorization=f"Bearer {hostile}"), policy)
    assert weird.startswith("sub:")
    assert "\n" not in weird and "{" not in weird
    # Empty subs are never recorded.
    policy.record_verified("tok", None)
    assert policy.verified_subject("tok") is None


async def test_auth_middleware_feeds_verified_subjects_to_tenancy():
    """The wiring behind the sub buckets: a token that passes the auth
    middleware's signature verification is recorded into the tenant
    policy; a rejected token never is."""
    from inference_gateway_tpu.api.middlewares.auth import JWTError, oidc_auth_middleware
    from inference_gateway_tpu.netio.server import Request, Response

    policy = TenantPolicy(TenantConfig(enabled=True))
    good, bad = _jwt("team-a"), _jwt("mallory-as-team-a")

    class FakeAuthenticator:
        async def verify(self, token):
            if token == good:
                return {"sub": "team-a"}
            raise JWTError("signature verification failed")

    mw = oidc_auth_middleware(FakeAuthenticator(), tenancy=policy)

    async def handler(req):
        return Response.json({})

    def request(token):
        return Request(method="POST", path="/v1/chat/completions", query={},
                       headers=_headers(authorization=f"Bearer {token}"),
                       body=b"")

    resp = await mw(request(good), handler)
    assert resp.status == 200
    assert derive_tenant(_headers(authorization=f"Bearer {good}"),
                         policy) == "sub:team-a"
    resp = await mw(request(bad), handler)
    assert resp.status == 401
    assert policy.verified_subject(bad) is None
    assert derive_tenant(_headers(authorization=f"Bearer {bad}"),
                         policy).startswith("key:")


def test_tenant_policy_weights_and_quota():
    policy = TenantPolicy(TenantConfig(
        enabled=True, weights="noisy:10,quiet:0.5,bad:x,:3", quota_base=4))
    assert policy.weight("noisy") == 10.0
    assert policy.weight("quiet") == 0.5
    assert policy.weight("bad") == 1.0  # unparseable entry -> default
    assert policy.weight("unknown") == 1.0
    assert policy.quota("noisy") == 40
    assert policy.quota("quiet") == 2
    assert policy.quota("unknown") == 4
    snap = policy.snapshot()
    assert snap["enabled"] and snap["quota_base"] == 4
    assert TenantPolicy(TenantConfig(enabled=True)).quota("any") == 0  # quotas off


# ---------------------------------------------------------------------------
# Fairness + quota on the admission ledger (VirtualClock, zero sleeps)
# ---------------------------------------------------------------------------
def _tenant_controller(shared=None, **tenant_kw):
    cfg = OverloadConfig(
        max_concurrent_streaming=4, queue_depth_streaming=4,
        max_concurrent_buffered=4, queue_depth_buffered=4,
        queue_timeout=5.0, shed_high_water=1.0, engine_depth_high_water=0,
        drain_deadline=30.0, drain_retry_after=1.0)
    policy = TenantPolicy(TenantConfig(enabled=True, **tenant_kw))
    return OverloadController(cfg, clock=VirtualClock(), tenancy=policy,
                              shared=shared)


async def test_noisy_tenant_sheds_against_itself_never_the_quiet_one():
    """THE fairness acceptance: a 10×-weight noisy tenant at 2× the
    class cap's offered load saturates the class and is shed against
    itself; the quiet tenant is never shed — it queues and takes the
    next released slot (handover)."""
    ctrl = _tenant_controller(weights="noisy:10")
    tickets, sheds = [], []
    for _ in range(8):  # 2x the streaming cap of 4
        try:
            tickets.append(await ctrl.admit(
                CLASS_STREAMING, PRIORITY_INTERACTIVE, tenant="noisy"))
        except AdmissionRejectedError as e:
            sheds.append(e)
    assert len(tickets) == 4 and len(sheds) == 4
    assert {e.reason for e in sheds} == {"tenant_fair_share"}
    assert all(e.status == 429 for e in sheds)

    # The quiet tenant holds nothing -> NEVER shed: it queues.
    task = asyncio.ensure_future(ctrl.admit(
        CLASS_STREAMING, PRIORITY_INTERACTIVE, tenant="quiet"))
    for _ in range(3):
        await asyncio.sleep(0)
    assert not task.done()
    tickets.pop().release()  # handover: quiet takes the freed slot
    quiet = await task
    snap = ctrl.snapshot()
    assert snap["tenants_in_flight"] == {"noisy": 3, "quiet": 1}
    assert snap["classes"][CLASS_STREAMING]["in_flight"] == 4
    for t in tickets:
        t.release()
    quiet.release()
    assert ctrl.snapshot().get("tenants_in_flight") == {}


async def test_fair_share_floor_is_one_slot_at_saturation():
    """At saturation every tenant's fair share floors at one slot: a
    tenant already holding one is shed on its second request, however
    small its weight — and that IS its fair share, not starvation."""
    ctrl = _tenant_controller(weights="noisy:10")
    noisy = [await ctrl.admit(CLASS_STREAMING, PRIORITY_INTERACTIVE, tenant="noisy")
             for _ in range(3)]
    quiet = await ctrl.admit(CLASS_STREAMING, PRIORITY_INTERACTIVE, tenant="quiet")
    with pytest.raises(AdmissionRejectedError) as exc:
        await ctrl.admit(CLASS_STREAMING, PRIORITY_INTERACTIVE, tenant="quiet")
    assert exc.value.reason == "tenant_fair_share"
    # Below the cap nobody is fairness-shed at all.
    quiet.release()
    noisy[0].release()
    again = await ctrl.admit(CLASS_STREAMING, PRIORITY_INTERACTIVE, tenant="quiet")
    again.release()
    for t in noisy[1:]:
        t.release()


async def test_tenant_quota_caps_in_flight_per_tenant():
    ctrl = _tenant_controller(weights="big:2", quota_base=1)
    big = [await ctrl.admit(CLASS_STREAMING, PRIORITY_INTERACTIVE, tenant="big")
           for _ in range(2)]  # quota = base 1 x weight 2
    with pytest.raises(AdmissionRejectedError) as exc:
        await ctrl.admit(CLASS_STREAMING, PRIORITY_INTERACTIVE, tenant="big")
    assert exc.value.reason == "tenant_quota" and exc.value.status == 429
    # Another tenant's quota is untouched.
    other = await ctrl.admit(CLASS_STREAMING, PRIORITY_INTERACTIVE, tenant="other")
    for t in [*big, other]:
        t.release()


async def test_tenant_quota_counts_cluster_wide_through_the_segment():
    """Quota occupancy reads the SHARED tenant cells: holds on a peer
    worker's slab count against this worker's admission decision."""
    name = _name()
    seg = ClusterSegment.create(name, workers=2)
    try:
        seg.begin_generation(0, 1)
        seg.begin_generation(1, 2)
        ctrl = _tenant_controller(shared=seg.slab(0), quota_base=2)
        slot = tenant_slot("big", seg.tenant_slots)
        seg.slab(1).tenant_add(slot, 2)  # peer worker already holds 2
        with pytest.raises(AdmissionRejectedError) as exc:
            await ctrl.admit(CLASS_STREAMING, PRIORITY_INTERACTIVE, tenant="big")
        assert exc.value.reason == "tenant_quota"
        # The peer dies; its generation is reaped -> quota frees up.
        seg.reap(1)
        ticket = await ctrl.admit(CLASS_STREAMING, PRIORITY_INTERACTIVE, tenant="big")
        assert seg.tenant_total(slot) == 1  # mirrored from THIS worker
        ticket.release()
        assert seg.tenant_total(slot) == 0
    finally:
        seg.close(unlink=True)


async def test_tenant_kill_switch_stops_rejections():
    """TENANT_ENABLED=false is the isolation kill switch: no quota or
    fairness rejections, tenant buckets are simply not consulted."""
    cfg = OverloadConfig(max_concurrent_streaming=4, queue_depth_streaming=8,
                         queue_timeout=5.0)
    ctrl = OverloadController(
        cfg, clock=VirtualClock(),
        tenancy=TenantPolicy(TenantConfig(enabled=False, quota_base=1,
                                          weights="noisy:10")))
    tickets = [await ctrl.admit(CLASS_STREAMING, PRIORITY_INTERACTIVE, tenant="noisy")
               for _ in range(4)]
    assert ctrl.snapshot().get("tenants_in_flight") is None
    for t in tickets:
        t.release()


async def test_admission_counters_mirror_into_the_slab():
    """Every admit/queue/shed/release transition lands in the shared
    cells, conservation-exact — the /metrics merge and the crash reaper
    read these, so drift here is a phantom-load bug."""
    name = _name()
    seg = ClusterSegment.create(name, workers=1)
    try:
        seg.begin_generation(0, 1)
        ctrl = _tenant_controller(shared=seg.slab(0))
        tickets = [await ctrl.admit(CLASS_STREAMING, PRIORITY_INTERACTIVE,
                                    tenant="t") for _ in range(4)]
        assert seg.counter_total("in_flight_streaming") == 4
        assert seg.counter_total("admitted_total") == 4
        task = asyncio.ensure_future(ctrl.admit(
            CLASS_STREAMING, PRIORITY_INTERACTIVE, tenant="u"))
        for _ in range(3):
            await asyncio.sleep(0)
        assert seg.counter_total("queued_streaming") == 1
        tickets.pop().release()
        (await task).release()
        for t in tickets:
            t.release()
        totals = seg.totals()
        assert totals["in_flight_streaming"] == 0
        assert totals["queued_streaming"] == 0
        assert totals["admitted_total"] == 5
        assert seg.tenant_totals() == {}
    finally:
        seg.close(unlink=True)


# ---------------------------------------------------------------------------
# Supervisor against real scripted workers
# ---------------------------------------------------------------------------
def _child_env() -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO_ROOT)
    return env


def _idle_spawn(name: str, workers: int, extra: tuple = ()):
    def spawn(index: int, generation: int):
        return subprocess.Popen(
            [sys.executable, "-m", "inference_gateway_tpu.cluster.worker",
             "--idle", name, str(workers), str(index), "--interval", "0.05",
             *extra],
            cwd=str(REPO_ROOT), env=_child_env())
    return spawn


def _stop_supervisor(sup: Supervisor) -> None:
    asyncio.run(sup.stop())


def test_supervisor_respawns_exited_worker():
    name = _name()
    seg = ClusterSegment.create(name, workers=1)
    sup = Supervisor(seg, _idle_spawn(name, 1, ("--exit-after", "3")),
                     heartbeat_timeout=0, check_interval=0.05)
    try:
        sup.start()
        first = sup.workers[0]
        assert seg.generation(0) == first.generation == 1
        assert _wait(lambda: bool(sup.check_once()))
        assert sup.respawns >= 1
        replacement = sup.workers[0]
        assert replacement.generation > first.generation
        assert seg.generation(0) == replacement.generation
        assert replacement.proc.pid != first.proc.pid
        assert replacement.restarts == first.restarts + 1
    finally:
        _stop_supervisor(sup)
        seg.close(unlink=True)


def test_supervisor_replaces_wedged_worker_via_heartbeat_staleness():
    """A worker that stays alive but stops beating (wedged event loop)
    is killed the hard way and respawned — poll() alone would never
    catch it."""
    name = _name()
    seg = ClusterSegment.create(name, workers=1)
    sup = Supervisor(seg, _idle_spawn(name, 1, ("--wedge-after", "2")),
                     heartbeat_timeout=0.4, check_interval=0.05)
    try:
        sup.start()
        first_pid = sup.workers[0].proc.pid
        assert _wait(lambda: bool(sup.check_once()))
        assert sup.respawns >= 1
        assert sup.workers[0].proc.pid != first_pid
    finally:
        _stop_supervisor(sup)
        seg.close(unlink=True)


def test_boot_grace_tolerates_slow_first_heartbeat():
    """A worker whose first beat lands after heartbeat_timeout (slow
    build_gateway / MCP init / listener bind) must NOT be crash-looped:
    boots get their own (larger) deadline, and staleness only arms once
    the first real beat has been observed."""
    clock = VirtualClock()
    name = _name()
    seg = ClusterSegment.create(name, workers=1)

    def spawn(index: int, generation: int):
        # A process that stays alive but never attaches or beats — the
        # slab holds only the supervisor's spawn stamp.
        return subprocess.Popen([sys.executable, "-c",
                                 "import time; time.sleep(300)"])

    sup = Supervisor(seg, spawn, heartbeat_timeout=1.0, boot_timeout=30.0,
                     check_interval=0.05, clock=clock)
    try:
        sup.start()
        # Way past the steady-state heartbeat timeout but inside the
        # boot window: not stale (pre-fix this was judged wedged and
        # respawned — a permanent crash loop for any slow boot).
        clock.advance(10.0)
        assert sup.check_once() == []
        # The first beat arms staleness, measured from the beat.
        seg.slab(0).beat(clock.now())
        clock.advance(0.5)
        assert sup.check_once() == []
        clock.advance(1.0)
        assert sup.check_once() == [0]  # genuinely stale -> replaced
        # A replacement that never beats at all is still caught — at
        # the boot deadline instead of the heartbeat one.
        clock.advance(29.0)
        assert sup.check_once() == []
        clock.advance(2.0)
        assert sup.check_once() == [0]
    finally:
        _stop_supervisor(sup)
        seg.close(unlink=True)


def test_rolling_restart_does_not_race_the_monitor():
    """The orchestrated-restart race: with the monitor task running, a
    rolling restart must be the ONLY thing respawning the slots it
    cycles. Pre-fix, the SIGTERM'd exit woke check_once via SIGCHLD,
    which reaped + respawned first; rolling_restart then zeroed the
    LIVE replacement's slab and spawned a second, unsupervised process
    writing the same single-writer slab."""
    name = _name()
    seg = ClusterSegment.create(name, workers=2)
    sup = Supervisor(seg, _idle_spawn(name, 2), heartbeat_timeout=0,
                     check_interval=0.01, term_grace=15.0)

    async def scenario():
        sup.start()
        monitor = asyncio.get_running_loop().create_task(sup.run())
        assert await _await(
            lambda: all(seg.heartbeat(i) > sup.workers[i].started
                        for i in sup.workers))
        old = {i: sup.workers[i].proc for i in sup.workers}
        await sup.rolling_restart()
        # The monitor never respawned anything itself -> no double
        # spawn, no orphaned second writer: exactly one replacement per
        # slot (initial generations 1,2; replacements 3,4).
        assert sup.respawns == 0
        assert sup._next_generation == 5
        for i, proc in old.items():
            assert proc.poll() is not None  # old worker fully gone
            fresh = sup.workers[i]
            assert fresh.proc.pid != proc.pid
            assert fresh.proc.poll() is None  # exactly one live replacement
            assert seg.generation(i) == fresh.generation
        await sup.stop()
        monitor.cancel()

    asyncio.run(scenario())
    seg.close(unlink=True)


def test_overlapping_rolling_restarts_coalesce():
    """Rapid SIGHUPs must not stack rolling restarts over the same
    slots: a second invocation while one is in progress is a no-op."""
    name = _name()
    seg = ClusterSegment.create(name, workers=2)
    sup = Supervisor(seg, _idle_spawn(name, 2), heartbeat_timeout=0,
                     check_interval=0.05, term_grace=15.0)

    async def scenario():
        sup.start()
        await asyncio.gather(sup.rolling_restart(), sup.rolling_restart())
        assert sup._next_generation == 5  # each slot restarted exactly once
        assert not sup.rolling
        await sup.stop()

    asyncio.run(scenario())
    seg.close(unlink=True)


_LEAK_CHILD = textwrap.dedent("""
    import os, sys, time
    from inference_gateway_tpu.cluster.shm import ClusterSegment
    name, generation = sys.argv[1], int(sys.argv[2])
    seg = ClusterSegment.attach(name, workers=1)
    slab = seg.slab(0)
    if generation == 1:
        # First life: take admission holds, then get SIGKILLed with
        # them still open — the abrupt-death ticket leak.
        slab.add("in_flight_streaming", 1)
        slab.add("in_flight_buffered", 1)
        slab.add("admitted_total", 2)
        slab.tenant_add(3, 1)
    slab.beat(time.monotonic())
    print("ready", flush=True)
    time.sleep(300)
""")


def test_sigkilled_worker_tickets_and_gauges_reclaimed_by_reap():
    """The ticket-leak regression (ISSUE 16 satellite): a worker dies
    abruptly holding admission tickets and a tenant quota hold; the
    supervisor's generation reap reclaims every one — cluster totals
    and the tenant gauge series drop the dead worker's contribution
    within one monitor pass."""
    name = _name()
    seg = ClusterSegment.create(name, workers=1)

    def spawn(index: int, generation: int):
        return subprocess.Popen(
            [sys.executable, "-c", _LEAK_CHILD, name, str(generation)],
            stdout=subprocess.PIPE, cwd=str(REPO_ROOT), env=_child_env())

    sup = Supervisor(seg, spawn, heartbeat_timeout=0, check_interval=0.05)
    try:
        sup.start()
        proc = sup.workers[0].proc
        assert proc.stdout.readline().strip() == b"ready"
        assert seg.counter_total("in_flight_streaming") == 1
        assert seg.tenant_total(3) == 1
        assert 'cluster_tenant_in_flight{slot="3"} 1' in seg.render_prometheus(0.0)

        os.kill(proc.pid, signal.SIGKILL)
        assert _wait(lambda: bool(sup.check_once()))
        # One monitor pass reclaimed the tickets, the quota hold, and
        # the gauge series (no dead-worker residue on any surface).
        totals = seg.totals()
        assert totals.get("in_flight_streaming", 0) == 0
        assert totals.get("in_flight_buffered", 0) == 0
        assert seg.tenant_total(3) == 0
        assert "cluster_tenant_in_flight" not in seg.render_prometheus(0.0)
        # The replacement (generation 2) is alive with a clean slab.
        assert sup.workers[0].proc.stdout.readline().strip() == b"ready"
        assert seg.counter_total("admitted_total") == 0
    finally:
        _stop_supervisor(sup)
        seg.close(unlink=True)


# ---------------------------------------------------------------------------
# Real-process e2e: supervisor + 2 SO_REUSEPORT gateway workers + sidecar
# ---------------------------------------------------------------------------
def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.fixture(scope="module")
def cluster_stack(aloop):
    engine = Engine(EngineConfig(model="test-tiny", max_slots=4, max_seq_len=160,
                                 dtype="float32", max_prefill_batch=2,
                                 use_mesh=False, decode_chunk=2))
    access_log = AccessLog(service="tpu-sidecar", tail_size=128)
    sidecar = SidecarServer(engine, served_model_name="test-tiny",
                            access_log=access_log)
    sidecar_port = aloop.run(sidecar.start("127.0.0.1", 0))

    port = _free_port()
    metrics_port = _free_port()
    name = _name()
    seg = ClusterSegment.create(name, workers=2)
    spawn = gateway_spawn(name, 2, extra_env={
        "PYTHONPATH": str(REPO_ROOT),
        "TPU_API_URL": f"http://127.0.0.1:{sidecar_port}/v1",
        "OLLAMA_API_URL": "http://127.0.0.1:1/v1",
        "LLAMACPP_API_URL": "http://127.0.0.1:1/v1",
        "SERVER_HOST": "127.0.0.1",
        "SERVER_PORT": str(port),
        "TELEMETRY_ENABLE": "true",
        "TELEMETRY_TRACING_ENABLE": "true",
        "TELEMETRY_METRICS_PORT": str(metrics_port),
        "TENANT_ENABLED": "true",
        "CLUSTER_HEARTBEAT_INTERVAL": "200ms",
        "RESILIENCE_PROBE_ENABLED": "false",
        "DRAIN_DEADLINE": "3s",
    })
    sup = Supervisor(seg, spawn, heartbeat_timeout=10.0, check_interval=0.2,
                     term_grace=8.0)
    aloop.run(_async_call(sup.start))
    monitor = asyncio.run_coroutine_threadsafe(sup.run(), aloop.loop)
    assert _wait(lambda: _fleet_ready(seg, 2), timeout=120), \
        "gateway workers never became ready"
    yield seg, sup, port, metrics_port, sidecar, access_log
    aloop.run(sup.stop())
    monitor.cancel()
    seg.close(unlink=True)
    aloop.run(sidecar.shutdown())


async def _async_call(fn):
    return fn()


def _fleet_ready(seg: ClusterSegment, n: int) -> bool:
    """All n workers live AND past boot: their runtime published a blob
    (which happens only after the SO_REUSEPORT listeners are bound)."""
    if len(seg.live()) != n:
        return False
    blobs = seg.blobs()
    return all((blobs.get(i) or {}).get("pid") for i in range(n))


def _chat_body(max_tokens=24, **extra) -> dict:
    return {"model": "tpu/test-tiny", "stream": True, "temperature": 0,
            "max_tokens": max_tokens,
            "stream_options": {"include_usage": True},
            "messages": [{"role": "user", "content": "splice me"}], **extra}


# The continuation resumes from the TEXT the relay forwarded, which the sidecar
# re-encodes, so the prefix must round-trip through the byte tokenizer whatever
# number of frames reached the client before the kill (2 on a quiet machine, 3-5
# under load: an invalid UTF-8 byte decodes to U+FFFD, three bytes re-encoded,
# and the spliced stream diverged: 3 of 9 runs beside 14 busy processes, on the
# parent of ISSUE 40 as on its tree). Banning ids 128..255 keeps the greedy
# stream ASCII, as tests/test_stream_continuation.py's e2e does.
_ASCII_ONLY = {str(i): -100 for i in range(128, 256)}


def _parse_frames(body: bytes):
    frames = []
    for part in body.split(b"\n\n"):
        part = part.strip()
        if not part.startswith(b"data:"):
            continue
        payload = part[5:].strip()
        frames.append((part + b"\n\n",
                       None if payload == b"[DONE]" else json.loads(payload)))
    return frames


async def test_cluster_serves_and_merges_across_workers(cluster_stack):
    seg, _sup, port, metrics_port, _sidecar, _log = cluster_stack
    client = HTTPClient()
    resp = await client.get(f"http://127.0.0.1:{port}/health")
    assert resp.status == 200
    # Non-streamed request through the SO_REUSEPORT edge.
    resp = await client.get(f"http://127.0.0.1:{port}/v1/models?provider=tpu")
    assert resp.status == 200
    assert resp.json()["data"][0]["id"] == "tpu/test-tiny"
    # Whichever worker the scrape lands on, the cluster series merge
    # all live slabs — the per-worker metric merge surface.
    resp = await client.get(f"http://127.0.0.1:{metrics_port}/metrics")
    assert resp.status == 200
    text = resp.body.decode()
    assert 'cluster_worker_up{worker="0"} 1' in text
    assert 'cluster_worker_up{worker="1"} 1' in text
    assert 'cluster_admission{counter="in_flight_streaming"}' in text
    # /debug/status carries the merged cluster section.
    resp = await client.get(f"http://127.0.0.1:{metrics_port}/debug/status")
    assert resp.status == 200
    cluster = resp.json()["cluster"]
    assert cluster["live"] == [0, 1]
    assert cluster["self_worker"] in (0, 1)


async def test_sigkill_one_worker_drops_zero_non_streamed_requests(cluster_stack):
    """Availability acceptance: SIGKILL 1 of 2 workers, then hammer
    non-streamed requests — every one succeeds (the dead listener
    leaves the SO_REUSEPORT group with the process; the survivor takes
    all accepts) while the supervisor respawns the replacement."""
    seg, sup, port, _mp, _sidecar, _log = cluster_stack
    respawns_before = sup.respawns
    victim = seg.live()[0]
    victim_gen = seg.generation(victim)
    handle = sup.workers[victim]
    os.kill(seg.pid(victim), signal.SIGKILL)
    # "Beyond those in flight": a SYN racing the dying listener's fd
    # teardown lands in the corpse's accept queue and is lost with it —
    # that connection was in flight at the instant of death. The
    # acceptance is about everything AFTER the process is gone.
    assert await _await(lambda: handle.proc.poll() is not None, timeout=30)
    for i in range(20):
        client = HTTPClient()  # fresh pool: no keep-alive to the corpse
        resp = await client.get(f"http://127.0.0.1:{port}/health")
        assert resp.status == 200, f"request {i} dropped after worker kill"
        if i % 5 == 0:
            resp = await client.get(
                f"http://127.0.0.1:{port}/v1/models?provider=tpu")
            assert resp.status == 200
    # The supervisor reaps and respawns; the fleet heals to 2.
    assert await _await(lambda: sup.respawns > respawns_before, timeout=30)
    assert await _await(lambda: _fleet_ready(seg, 2), timeout=120)
    assert seg.generation(victim) > victim_gen


async def _kill_relay_mid_stream(seg, sidecar, url, body, headers):
    """Stream a chat completion and SIGKILL the worker relaying it after
    two content frames. The kill waits on state, not on time: the
    sidecar's scheduler thread is wedged at a scripted decode submit
    (``EngineFaultInjector`` "hang", 15 of the request's 96 tokens out),
    so the stream CANNOT finish until the relay's connection is gone and
    the wedge is released, however slowly this task is scheduled. Returns
    (raw frames kept, their content deltas, victim index)."""
    inj = EngineFaultInjector(sidecar.engine)
    inj.at("decode_submit", inj.calls["decode_submit"] + 8, "hang")
    client = HTTPClient()
    buf, got, contents, victim = b"", b"", [], None
    try:
        # The limit is for a wedged stream that never shows two content
        # frames: its TimeoutError is raised out here, past the except.
        async with asyncio.timeout(120):
            try:
                resp = await client.post(url, body, headers=headers, stream=True)
                assert resp.status == 200
                async for block in resp.iter_raw():
                    buf += block
                    while b"\n\n" in buf:
                        raw, buf = buf.split(b"\n\n", 1)
                        raw += b"\n\n"
                        got += raw
                        payload = raw.strip()[5:].strip()
                        if payload != b"[DONE]":
                            ev = json.loads(payload)
                            delta = ((ev.get("choices") or [{}])[0].get("delta") or {})
                            if delta.get("content"):
                                contents.append(delta["content"])
                    if len(contents) >= 2 and victim is None:
                        victim = next((i for i in seg.live()
                                       if seg.worker_counter(i, "in_flight_streaming") > 0), None)
                        assert victim is not None, "no worker holds the stream ticket"
                        os.kill(seg.pid(victim), signal.SIGKILL)
            except (HTTPClientError, OSError, ConnectionError, asyncio.IncompleteReadError):
                pass
    finally:
        inj.uninstall()  # releases the wedge: the sidecar runs on
    assert victim is not None, "stream finished before the kill landed"
    assert b"[DONE]" not in got, "stream finished before the kill landed"
    return got, contents, victim


async def test_sigkill_mid_stream_completes_byte_identical_via_continuation(
        cluster_stack):
    """THE chaos acceptance: SIGKILL the worker relaying an SSE stream
    after the first bytes; the client finishes the stream through a
    continuation request served by the survivor — byte-identical to an
    unkilled run, one trace id across the kill, continuation tokens
    billed exactly once, and the dead worker's streaming ticket
    reclaimed within one reap interval."""
    seg, sup, port, _mp, sidecar, access_log = cluster_stack
    url = f"http://127.0.0.1:{port}/v1/chat/completions"
    headers = Headers()
    headers.set("Content-Type", "application/json")
    headers.set("traceparent", TRACEPARENT)

    # 96 tokens: room for the wedge _kill_relay_mid_stream places at 15.
    body = json.dumps(_chat_body(max_tokens=96, logit_bias=_ASCII_ONLY)).encode()

    # Reference run, unkilled.
    client = HTTPClient()
    resp = await client.post(url, body, headers=headers, stream=True)
    assert resp.status == 200
    unkilled = b""
    async for block in resp.iter_raw():
        unkilled += block
    frames = _parse_frames(unkilled)
    usage = next(ev["usage"] for _r, ev in frames if ev and ev.get("usage"))
    assert usage["completion_tokens"] >= 6

    # Killed run: read a few content frames, SIGKILL the worker that
    # holds the streaming ticket (visible in its shared slab), keep
    # whatever complete frames arrived.
    got, contents, _victim = await _kill_relay_mid_stream(
        seg, sidecar, url, body, headers)

    # Ticket reclaim within one reap interval (ISSUE 16 acceptance).
    assert await _await(lambda: seg.counter_total("in_flight_streaming") == 0,
                        timeout=30)

    # Continuation splice: re-issue against the survivor with the
    # relayed prefix under the ORIGINAL id — PR 9's resume contract.
    kept = _parse_frames(got)
    cid, created = kept[0][1]["id"], kept[0][1]["created"]
    prefix = "".join(contents)
    cont_body = _chat_body(max_tokens=96, logit_bias=_ASCII_ONLY,
                           continuation={"text": prefix, "id": cid,
                                         "created": created})
    client = HTTPClient()
    resp = await client.post(url, json.dumps(cont_body).encode(),
                             headers=headers, stream=True)
    assert resp.status == 200
    continued = b""
    async for block in resp.iter_raw():
        continued += block
    cont_frames = _parse_frames(continued)
    assert (cont_frames[0][1]["choices"][0]["delta"] or {}).get("role") == "assistant"
    assert cont_frames[0][1]["id"] == cid  # ONE completion id spans the kill

    # Byte-identity: kept frames + continuation past its role preamble
    # must equal the unkilled run, modulo the per-run envelope identity
    # (fresh runs mint fresh ids/created).
    spliced = got + b"".join(raw for raw, _ev in cont_frames[1:])

    def normalize(raw_body: bytes) -> bytes:
        fs = _parse_frames(raw_body)
        ids = {ev["id"] for _r, ev in fs if ev and ev.get("id")}
        created_set = {ev["created"] for _r, ev in fs if ev and "created" in ev}
        assert len(ids) == 1 and len(created_set) == 1, (ids, created_set)
        return (raw_body.replace(ids.pop().encode(), b"ID")
                .replace(b'"created":%d' % created_set.pop(), b'"created":0'))

    assert normalize(spliced) == normalize(unkilled)

    # One trace id across the kill: both sidecar establishments (the
    # killed relay's and the continuation's) logged the edge trace.
    edge_trace = TRACEPARENT.split("-")[1]
    lines = [e for e in access_log.tail
             if e.get("route") == "/v1/chat/completions" and e.get("trace_id")]
    assert len([e for e in lines if e["trace_id"] == edge_trace]) >= 2

    # Once-only billing: the continuation's sidecar line bills exactly
    # the tokens past the relayed prefix (the killed attempt's line is
    # disconnect-attributed asynchronously, so only this is exact).
    resume = len(sidecar.engine.tokenizer.encode(prefix, add_bos=False))
    assert 0 < resume < usage["completion_tokens"]
    assert any(e.get("output_tokens") == usage["completion_tokens"] - resume
               for e in access_log.tail
               if e.get("route") == "/v1/chat/completions")

    # The fleet heals for whoever runs next.
    assert await _await(lambda: _fleet_ready(seg, 2), timeout=120)


async def test_journey_survives_originating_worker_death(cluster_stack):
    """THE fleet-observability acceptance (ISSUE 18): SIGKILL the worker
    that admitted + relayed a stream's first bytes, splice it to
    completion on the survivor under the same propagated traceparent,
    then ask ANY worker for ``/debug/journey?trace_id=`` — the full
    admit → route → first_byte → (kill) → splice → finish chain reads
    back as ONE journey spanning both workers, with exactly one
    ``finished`` event carrying the billing (once-only by construction:
    the dead relay never reached its finally)."""
    seg, sup, port, metrics_port, sidecar, _log = cluster_stack
    trace = uuid.uuid4().hex  # fresh 32-hex id: this test's own journey
    headers = Headers()
    headers.set("Content-Type", "application/json")
    headers.set("traceparent", f"00-{trace}-1234567890abcdef-01")
    url = f"http://127.0.0.1:{port}/v1/chat/completions"
    body = json.dumps(_chat_body(max_tokens=96, logit_bias=_ASCII_ONLY)).encode()

    got, contents, victim = await _kill_relay_mid_stream(
        seg, sidecar, url, body, headers)
    assert await _await(lambda: seg.counter_total("in_flight_streaming") == 0,
                        timeout=30)

    # Continuation splice on the survivor, SAME traceparent.
    kept = _parse_frames(got)
    cid, created = kept[0][1]["id"], kept[0][1]["created"]
    prefix = "".join(contents)
    cont_body = _chat_body(max_tokens=96, logit_bias=_ASCII_ONLY,
                           continuation={"text": prefix, "id": cid,
                                         "created": created})
    client = HTTPClient()
    resp = await client.post(url, json.dumps(cont_body).encode(),
                             headers=headers, stream=True)
    assert resp.status == 200
    continued = b""
    async for block in resp.iter_raw():
        continued += block
    usage = next(ev["usage"] for _r, ev in _parse_frames(continued)
                 if ev and ev.get("usage"))

    # The journey answers from whichever worker the query lands on —
    # the victim is dead, its shm journey slots are not (reap() leaves
    # the journey region alone). Poll: the survivor's terminal journey
    # event lands in its finally, which may still be running when the
    # stream's last byte reaches the client.
    rec = None
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        client = HTTPClient()
        got_resp = await client.get(
            f"http://127.0.0.1:{metrics_port}/debug/journey?trace_id={trace}")
        if got_resp.status == 200:
            rec = got_resp.json()
            names = [e["event"] for e in rec["events"]]
            if names.count("finished") == 1:
                break
        await asyncio.sleep(0.1)
    assert rec is not None, "journey never became queryable"

    assert rec["trace_id"] == trace
    assert victim in rec["workers"] and len(rec["workers"]) == 2
    names = [e["event"] for e in rec["events"]]
    assert names.count("finished") == 1, names  # once-only billing
    victim_events = [e["event"] for e in rec["events"]
                     if e["worker"] == victim]
    surv_events = [e["event"] for e in rec["events"] if e["worker"] != victim]
    # The dead worker's half of the chain, read from its corpse's slots.
    assert victim_events[0] == "admitted"
    assert "routed" in victim_events and "first_byte" in victim_events
    assert "finished" not in victim_events  # died before its finally
    # The survivor's half: admitted again, splice evidence, completion.
    assert surv_events[0] == "admitted"
    assert "spliced" in surv_events and "routed" in surv_events
    assert surv_events[-1] == "finished"
    fin = next(e for e in rec["events"] if e["event"] == "finished")
    assert fin["ok"] is True and fin["status"] == 200
    assert fin["output_tokens"] == usage["completion_tokens"]
    spliced = next(e for e in rec["events"] if e["event"] == "spliced")
    assert spliced["continuation_id"] == cid
    assert spliced["prefix_chars"] == len(prefix)
    # Chain ordering holds across processes (shared monotonic timebase).
    assert names[0] == "admitted" and names[-1] == "finished"

    # The fleet heals for whoever runs next.
    assert await _await(lambda: _fleet_ready(seg, 2), timeout=120)


async def test_slo_burn_rate_moves_and_reads_identically_fleet_wide(
        cluster_stack):
    """SLO acceptance (ISSUE 18): inject availability faults for one
    keyed tenant (a provider whose upstream is a closed port), then
    scrape ``/metrics`` repeatedly — the SO_REUSEPORT group hands each
    fresh connection to an arbitrary worker, yet every scrape reports
    the SAME cluster-merged burn rate, because each worker self-publishes
    then merges every live peer's window counts at scrape time."""
    seg, _sup, port, metrics_port, _sidecar, _log = cluster_stack
    assert await _await(lambda: _fleet_ready(seg, 2), timeout=120)
    url = f"http://127.0.0.1:{port}/v1/chat/completions"
    headers = Headers()
    headers.set("Content-Type", "application/json")
    headers.set("X-API-Key", "sk-slo-burn-e2e")
    good = dict(_chat_body(max_tokens=4), stream=False)
    bad = dict(good, model="ollama/llama3")  # OLLAMA_API_URL -> port 1
    statuses = []
    for payload in (good, bad, bad, bad):
        client = HTTPClient()
        resp = await client.post(url, json.dumps(payload).encode(),
                                 headers=headers)
        statuses.append(resp.status)
    assert statuses[0] == 200 and all(s >= 500 for s in statuses[1:]), statuses

    # Let one heartbeat pass so every live blob carries the counts, then
    # scrape with fresh connections: whoever answers, same exposition.
    await asyncio.sleep(0.5)
    import re
    pat = re.compile(
        r'inference_gateway_slo_burn_rate\{slo="availability",'
        r'window="5m",tenant="(key:[^"]+)"\} ([0-9.e+-]+)')
    seen = []
    for _ in range(4):
        client = HTTPClient()
        resp = await client.get(f"http://127.0.0.1:{metrics_port}/metrics")
        assert resp.status == 200
        matches = pat.findall(resp.body.decode())
        assert matches, "no availability burn-rate series for the keyed tenant"
        seen.append(sorted(matches))
    # Moves under faults: 3 bad of 4 -> burn far above 1 (budget-burning).
    tenant, value = seen[0][0]
    assert float(value) > 1.0, seen[0]
    # Identical from any worker.
    assert all(s == seen[0] for s in seen[1:]), seen


async def test_tenant_labels_ride_the_edge_in_cluster_mode(cluster_stack):
    """TENANT_ENABLED=true in the workers: per-tenant occupancy lands
    in the shared tenant cells and the wide-event access log carries
    the tenant id — verified via the shared segment after a keyed
    request."""
    seg, _sup, port, _mp, _sidecar, _log = cluster_stack
    client = HTTPClient()
    headers = Headers()
    headers.set("Content-Type", "application/json")
    headers.set("X-API-Key", "sk-tenant-e2e")
    body = dict(_chat_body(max_tokens=4), stream=False)
    resp = await client.post(f"http://127.0.0.1:{port}/v1/chat/completions",
                             json.dumps(body).encode(), headers=headers)
    assert resp.status == 200
    assert resp.json()["usage"]["completion_tokens"] > 0
    # The hold was mirrored in and released back out. The worker
    # releases its ticket AFTER flushing the response body, so give the
    # write a moment to land in the segment rather than racing it.
    assert await _await(lambda: seg.tenant_totals() == {}, timeout=30), \
        seg.tenant_totals()
    assert seg.counter_total("admitted_total") > 0
