"""A `go test -race`-analog for the scheduler-thread/asyncio seam.

CPython has no TSan, so this harness enforces the locking DISCIPLINE
instead of detecting torn accesses: every shared mutable structure in
the serving hot path is replaced by a proxy that asserts, on every
mutation, that the access happens under the lock (or from the thread)
that owns it. Run a concurrent workload under instrumentation and any
discipline violation raises with the offending operation and thread —
the same contract `-race` gives the reference's Go code (SURVEY.md §5,
Taskfile.yml:109–112), enforced at the same seams:

- ``Scheduler._waiting`` / ``_free`` / ``queue_depth``: mutated only
  under the ``_wake`` condition (client threads submit; the scheduler
  thread admits).
- ``Scheduler._slots``: mutated only by the scheduler thread (reads
  from server threads — health, metrics — are GIL-atomic by design).
- ``PageAllocator`` mutating methods: only under ``Engine._lock``
  (prefill/decode dispatch sections and release_slot).

The harness swaps the scheduler's Condition and the engine's Lock for
RLock-backed equivalents so ownership is exact (`RLock._is_owned`),
then wraps the structures. `DisciplineViolation` failures are raised on
the offending thread AND recorded, so violations on the scheduler
thread (where raising would only kill the daemon) still fail the test.
"""

from __future__ import annotations

import threading
from collections import deque


class DisciplineViolation(AssertionError):
    pass


class _Recorder:
    def __init__(self):
        self.violations: list[str] = []
        self._lock = threading.Lock()

    def fail(self, msg: str) -> None:
        full = f"{msg} [thread={threading.current_thread().name}]"
        with self._lock:
            self.violations.append(full)
        raise DisciplineViolation(full)


class LockedDeque(deque):
    """Deque asserting every mutation happens under the owning lock."""

    def __init__(self, iterable, owned, recorder, name):
        super().__init__(iterable)
        self._owned = owned
        self._rec = recorder
        self._name = name

    def _check(self, op):
        if not self._owned():
            self._rec.fail(f"unlocked {op} on {self._name}")

    def append(self, x):
        self._check("append")
        return super().append(x)

    def appendleft(self, x):
        self._check("appendleft")
        return super().appendleft(x)

    def popleft(self):
        self._check("popleft")
        return super().popleft()

    def pop(self):
        self._check("pop")
        return super().pop()

    def clear(self):
        self._check("clear")
        return super().clear()


class LockedList(list):
    def __init__(self, iterable, owned, recorder, name):
        super().__init__(iterable)
        self._owned = owned
        self._rec = recorder
        self._name = name

    def _check(self, op):
        if not self._owned():
            self._rec.fail(f"unlocked {op} on {self._name}")

    def append(self, x):
        self._check("append")
        return super().append(x)

    def pop(self, *a):
        self._check("pop")
        return super().pop(*a)

    def remove(self, x):
        self._check("remove")
        return super().remove(x)


class ThreadOwnedDict(dict):
    """Dict whose MUTATIONS must come from one designated thread."""

    def __init__(self, mapping, recorder, name):
        super().__init__(mapping)
        self.owner_thread: threading.Thread | None = None  # set after start()
        self._rec = recorder
        self._name = name

    def _check(self, op):
        if self.owner_thread is not None and threading.current_thread() is not self.owner_thread:
            self._rec.fail(
                f"{op} on {self._name} from non-owner thread "
                f"(owner={self.owner_thread.name})")

    def __setitem__(self, k, v):
        self._check("__setitem__")
        return super().__setitem__(k, v)

    def __delitem__(self, k):
        self._check("__delitem__")
        return super().__delitem__(k)

    def pop(self, *a):
        self._check("pop")
        return super().pop(*a)

    def clear(self):
        self._check("clear")
        return super().clear()


def instrument(scheduler, recorder: _Recorder | None = None) -> _Recorder:
    """Instrument a (not-yet-started) Scheduler + its Engine.

    Returns the recorder; call ``recorder.violations`` after the
    workload (empty == discipline held). Start the scheduler with
    ``start_instrumented(scheduler)`` so _slots learns its owner.
    """
    rec = recorder or _Recorder()

    # Exact lock ownership: RLock-backed condition / engine lock.
    wake = threading.Condition(threading.RLock())
    scheduler._wake = wake
    owned = wake._is_owned  # exact with RLock

    scheduler._waiting = LockedDeque(scheduler._waiting, owned, rec, "Scheduler._waiting")
    scheduler._free = LockedList(scheduler._free, owned, rec, "Scheduler._free")
    scheduler._slots = ThreadOwnedDict(scheduler._slots, rec, "Scheduler._slots")

    engine = scheduler.engine
    elock = threading.RLock()
    engine._lock = elock
    if engine.allocator is not None:
        alloc = engine.allocator
        for meth in ("ensure_capacity", "release", "adopt_pages"):
            orig = getattr(alloc, meth)

            def guarded(*a, _orig=orig, _name=meth, **kw):
                if not elock._is_owned():
                    rec.fail(f"PageAllocator.{_name} outside Engine._lock")
                return _orig(*a, **kw)

            setattr(alloc, meth, guarded)
    return rec


def start_instrumented(scheduler) -> None:
    scheduler.start()
    scheduler._slots.owner_thread = scheduler._thread


def hammer_registry(registry, writer_threads: int = 8, reader_threads: int = 2,
                    iters: int = 400) -> list[str]:
    """Concurrency hammer for the metrics ``Registry`` (ISSUE 3 satellite).

    The registry is mutated from every thread in the process — asyncio
    handlers, the scheduler thread's emit path, the metrics listener's
    scrapes — so its locking contract is load-bearing. N writer threads
    add/set/record against shared instruments (with overlapping label
    sets, including exposition-hostile label values) while reader threads
    collect() concurrently. Returns error strings; empty means no
    exceptions, no torn exposition, and exactly-conserved counter totals.
    """
    counter = registry.counter("race.hammer.counter", "hammer", ("k",))
    gauge = registry.gauge("race.hammer.gauge", "hammer", ("k",))
    hist = registry.histogram("race.hammer.hist", "hammer", ("k",), (0.1, 1.0, 10.0))
    errors: list[str] = []
    errors_lock = threading.Lock()
    barrier = threading.Barrier(writer_threads + reader_threads)

    def fail(msg: str) -> None:
        with errors_lock:
            errors.append(f"{msg} [thread={threading.current_thread().name}]")

    def writer(tid: int) -> None:
        barrier.wait()
        labels = {"k": f't{tid % 4}"\\\n'}  # escaping-hostile label value
        for i in range(iters):
            try:
                counter.add(1, labels)
                gauge.set(i, labels)
                hist.record((i % 23) / 2.0, labels)
            except Exception as e:
                fail(f"writer: {e!r}")
                return

    def reader() -> None:
        barrier.wait()
        for _ in range(iters):
            try:
                text = registry.expose()
                if "race_hammer_counter" not in text:
                    fail("counter series missing from exposition")
                    return
            except Exception as e:
                fail(f"reader: {e!r}")
                return

    threads = [threading.Thread(target=writer, args=(t,), name=f"hammer-w{t}", daemon=True)
               for t in range(writer_threads)]
    threads += [threading.Thread(target=reader, name=f"hammer-r{t}", daemon=True)
                for t in range(reader_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
        if t.is_alive():
            fail(f"{t.name} did not finish")
    total = sum(counter.values().values())
    if total != writer_threads * iters:
        fail(f"counter lost updates: {total} != {writer_threads * iters}")
    if hist.total_count() != writer_threads * iters:
        fail(f"histogram lost observations: {hist.total_count()} != {writer_threads * iters}")
    return errors


def hammer_scheduler_preempt(scheduler, submit_threads: int = 3,
                             per_thread: int = 8, timeout: float = 120.0) -> list[str]:
    """Concurrency hammer for the preemption/cancel seam (ISSUE 7).

    N submitter threads pour paged-mode requests into a pool sized so
    organic KV-pressure preemption fires, while a canceller thread flips
    ``disconnected`` on live requests (early-terminate) mid-decode.
    Invariants: every request reaches EXACTLY ONE terminal callback with
    a known reason, no request exceeds the preemption budget, and the
    slot pool is fully restored after the drain. Run under instrument()
    so every preemption-path mutation is also discipline-checked.
    """
    import queue
    import time

    from inference_gateway_tpu.serving.scheduler import GenRequest

    errors: list[str] = []
    errors_lock = threading.Lock()
    terminal: dict[str, list] = {}
    done: "queue.Queue[str]" = queue.Queue()
    live: list = []
    stop_cancel = threading.Event()
    total = submit_threads * per_thread
    barrier = threading.Barrier(submit_threads + 1)

    def fail(msg: str) -> None:
        with errors_lock:
            errors.append(f"{msg} [thread={threading.current_thread().name}]")

    def submitter(tid: int) -> None:
        barrier.wait()
        for i in range(per_thread):
            rid = f"h{tid}-{i}"
            terminal[rid] = []

            def cb(tok, lp, fin, reason, rid=rid):
                if fin:
                    terminal[rid].append(reason)
                    done.put(rid)

            req = GenRequest(prompt_ids=[1 + (tid + i) % 7] * (18 + 5 * (i % 4)),
                             max_tokens=6 + 4 * (i % 3), callback=cb,
                             request_id=rid)
            live.append(req)
            try:
                scheduler.submit(req)
            except Exception as e:
                fail(f"submit: {e!r}")
                done.put(rid)
                terminal[rid].append("submit-error")

    def canceller() -> None:
        barrier.wait()
        n = 0
        while not stop_cancel.is_set():
            snapshot = list(live)
            if snapshot:
                snapshot[n % len(snapshot)].disconnected = True
                n += 1
            time.sleep(0.003)

    threads = [threading.Thread(target=submitter, args=(t,), name=f"preempt-s{t}",
                                daemon=True) for t in range(submit_threads)]
    threads.append(threading.Thread(target=canceller, name="preempt-cancel", daemon=True))
    for t in threads:
        t.start()
    deadline = time.monotonic() + timeout
    seen = 0
    while seen < total and time.monotonic() < deadline:
        try:
            done.get(timeout=max(deadline - time.monotonic(), 0.1))
            seen += 1
        except queue.Empty:
            break
    stop_cancel.set()
    if seen < total:
        fail(f"only {seen}/{total} requests reached a terminal callback")
    for rid, reasons in terminal.items():
        if len(reasons) != 1:
            fail(f"{rid}: {len(reasons)} terminal callbacks ({reasons})")
        elif reasons[0] not in ("stop", "length", "error", "disconnected"):
            fail(f"{rid}: unexpected terminal reason {reasons[0]!r}")
    for req in live:
        if req.preempt_count > scheduler.preempt_max:
            fail(f"{req.request_id}: preempt_count {req.preempt_count} "
                 f"exceeds budget {scheduler.preempt_max}")
    # Drain: every slot back in the pool, every page free.
    deadline = time.monotonic() + 15
    while scheduler.active_requests() and time.monotonic() < deadline:
        time.sleep(0.02)
    if sorted(scheduler._free) != list(range(scheduler.engine.config.max_slots)):
        fail(f"slot pool not restored: {sorted(scheduler._free)}")
    alloc = scheduler.engine.allocator
    if alloc is not None and alloc.free_page_count() != alloc.num_pages:
        fail(f"page pool leaked: {alloc.free_page_count()}/{alloc.num_pages} free")
    return errors


def hammer_profiler(lifecycle_threads: int = 3, reader_threads: int = 3,
                    iters: int = 25) -> list[str]:
    """Concurrency hammer for the sampling profiler (ISSUE 4 satellite).

    The profiler's lifecycle is driven from asyncio handlers, shutdown
    paths, and its own sampler thread simultaneously, so concurrent
    start/sample/stop must neither raise, tear a window, nor leak a
    sampler thread. N lifecycle threads cycle start_continuous/stop and
    run blocking on-demand captures while reader threads hit snapshot()
    and stats(). Returns error strings; the caller also asserts no
    sampler thread survives the final stop().
    """
    from inference_gateway_tpu.otel.profiling import SamplingProfiler

    prof = SamplingProfiler(hz=397.0, window_s=0.02, windows=4, max_stacks=128)
    # Another fixture's continuous profiler may be live in this process;
    # only threads spawned during the hammer count as leaks.
    pre_existing = {t for t in threading.enumerate() if t.name == "profiler-sampler"}
    errors: list[str] = []
    errors_lock = threading.Lock()
    barrier = threading.Barrier(lifecycle_threads + reader_threads)

    def fail(msg: str) -> None:
        with errors_lock:
            errors.append(f"{msg} [thread={threading.current_thread().name}]")

    def lifecycle(tid: int) -> None:
        barrier.wait()
        for i in range(iters):
            try:
                if (i + tid) % 3 == 0:
                    prof.start_continuous()
                elif (i + tid) % 3 == 1:
                    window = prof.profile(0.002, hz=397.0)
                    if window.samples <= 0:
                        fail("on-demand capture took no samples")
                        return
                else:
                    prof.stop()
            except Exception as e:
                fail(f"lifecycle: {e!r}")
                return

    def reader() -> None:
        barrier.wait()
        for _ in range(iters * 2):
            try:
                prof.snapshot()
                prof.stats()
            except Exception as e:
                fail(f"reader: {e!r}")
                return

    threads = [threading.Thread(target=lifecycle, args=(t,), name=f"prof-l{t}", daemon=True)
               for t in range(lifecycle_threads)]
    threads += [threading.Thread(target=reader, name=f"prof-r{t}", daemon=True)
                for t in range(reader_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
        if t.is_alive():
            fail(f"{t.name} did not finish")
    prof.stop()
    leaked = [t for t in threading.enumerate()
              if t.name == "profiler-sampler" and t not in pre_existing]
    if leaked:
        fail(f"sampler thread leaked after stop(): {[t.name for t in leaked]}")
    return errors


def hammer_prober(prober, flip_threads: int = 4, reader_threads: int = 3,
                  iters: int = 500) -> list[str]:
    """Concurrency hammer for the health prober (ISSUE 9 satellite).

    Probe outcomes can land from concurrent probe rounds while the
    request path calls ``healthy()`` per candidate and /debug/status
    snapshots — the eject/readmit transition must be race-free. N
    flipper threads record random outcomes per target while readers
    call healthy()/snapshot() concurrently. Invariants at quiesce:
    no exceptions, and per target ``ejections - readmissions`` equals
    exactly 1 when ejected else 0 (transitions strictly alternate —
    a torn transition double-counts one side).
    """
    import random as _random

    errors: list[str] = []
    errors_lock = threading.Lock()
    barrier = threading.Barrier(flip_threads + reader_threads)

    def fail(msg: str) -> None:
        with errors_lock:
            errors.append(f"{msg} [thread={threading.current_thread().name}]")

    def flipper(tid: int) -> None:
        rng = _random.Random(1000 + tid)
        barrier.wait()
        for _ in range(iters):
            t = rng.choice(prober.targets)
            try:
                prober.record(t.provider, t.model, rng.random() < 0.5)
            except Exception as e:
                fail(f"flipper: {e!r}")
                return

    def reader() -> None:
        barrier.wait()
        for _ in range(iters):
            try:
                for t in prober.targets:
                    prober.healthy(t.provider, t.model)
                prober.snapshot()
            except Exception as e:
                fail(f"reader: {e!r}")
                return

    threads = [threading.Thread(target=flipper, args=(t,), name=f"probe-f{t}", daemon=True)
               for t in range(flip_threads)]
    threads += [threading.Thread(target=reader, name=f"probe-r{t}", daemon=True)
                for t in range(reader_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
        if t.is_alive():
            fail(f"{t.name} did not finish")
    for tgt in prober.snapshot()["targets"]:
        want = 1 if tgt["ejected"] else 0
        if tgt["ejections"] - tgt["readmissions"] != want:
            fail(f"{tgt['provider']}/{tgt['model']}: ejections={tgt['ejections']} "
                 f"readmissions={tgt['readmissions']} ejected={tgt['ejected']}")
        if not tgt["ejected"] and not prober.healthy(tgt["provider"], tgt["model"]):
            fail(f"{tgt['provider']}/{tgt['model']}: snapshot/healthy disagree")
    return errors


def hammer_shm_ledger(workers: int = 4, iters: int = 2000,
                      reader_threads: int = 2) -> list[str]:
    """Multi-PROCESS hammer for the cluster shm segment (ISSUE 16).

    The single-writer-per-slab discipline means no two processes ever
    write the same cell, so the hammer's job is different from the
    thread harnesses: prove that (a) concurrent writers on distinct
    slabs never corrupt each other's counters — exact conservation math
    holds at quiesce — and (b) readers merging the segment mid-storm
    (the /metrics scrape, /debug/status, the supervisor's staleness
    scan) never throw or observe a torn blob, thanks to the seqlock.

    N child processes (``python -m inference_gateway_tpu.cluster.shm
    --hammer``) each do ``iters`` increments of held/ops/tenant then
    ``iters - (index+1)`` decrements, leaving exact residues:
    ``held[i] == i+1``, ``ops[i] == 2*iters - (i+1)``, tenant slot
    ``i % 8`` accumulating ``i+1`` per mapped worker. Reader threads in
    the parent hammer totals()/blobs()/render_prometheus() throughout.
    Finally worker 0 is reaped and the totals must drop by exactly its
    residue — the crash-reclaim path the ticket-leak fix rides on.
    """
    import os
    import subprocess
    import sys
    import uuid

    from inference_gateway_tpu.cluster.shm import ClusterSegment

    errors: list[str] = []
    errors_lock = threading.Lock()

    def fail(msg: str) -> None:
        with errors_lock:
            errors.append(f"{msg} [thread={threading.current_thread().name}]")

    name = f"ig-hammer-{os.getpid()}-{uuid.uuid4().hex[:6]}"
    seg = ClusterSegment.create(name, workers=workers,
                                counters=("held", "ops"), tenant_slots=8,
                                blob_cap=1024)
    procs: list["subprocess.Popen[bytes]"] = []
    stop_readers = threading.Event()
    try:
        for i in range(workers):
            seg.begin_generation(i, i + 1)
        for i in range(workers):
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "inference_gateway_tpu.cluster.shm",
                 "--hammer", name, str(workers), str(i), str(iters)],
                cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

        def reader() -> None:
            while not stop_readers.is_set():
                try:
                    totals = seg.totals()
                    if totals.get("held", 0) < 0:
                        fail(f"negative held total: {totals}")
                    seg.tenant_totals()
                    for blob in seg.blobs().values():
                        if blob and "worker" not in blob:
                            fail(f"torn blob: {blob!r}")
                    seg.render_prometheus(0.0)
                    seg.status(0.0)
                except Exception as e:
                    fail(f"reader: {e!r}")
                    return

        readers = [threading.Thread(target=reader, name=f"shm-r{t}", daemon=True)
                   for t in range(reader_threads)]
        for t in readers:
            t.start()
        for i, p in enumerate(procs):
            try:
                rc = p.wait(timeout=120)
            except subprocess.TimeoutExpired:
                p.kill()
                fail(f"worker {i} hung")
                continue
            if rc != 0:
                fail(f"worker {i} exited {rc}")
        stop_readers.set()
        for t in readers:
            t.join(timeout=30)
            if t.is_alive():
                fail(f"{t.name} did not finish")
        if errors:
            return errors

        # Conservation at quiesce: exact residues, nothing lost or torn.
        held_want = workers * (workers + 1) // 2
        ops_want = 2 * workers * iters - held_want
        totals = seg.totals()
        if totals.get("held") != held_want:
            fail(f"held total {totals.get('held')} != {held_want}")
        if totals.get("ops") != ops_want:
            fail(f"ops total {totals.get('ops')} != {ops_want}")
        for i in range(workers):
            if seg.worker_counter(i, "held") != i + 1:
                fail(f"worker {i} held residue "
                     f"{seg.worker_counter(i, 'held')} != {i + 1}")
        tenant_want = [0] * 8
        for i in range(workers):
            tenant_want[i % 8] += i + 1
        got = seg.tenant_totals()
        for slot, want in enumerate(tenant_want):
            if got.get(slot, 0) != want:
                fail(f"tenant slot {slot}: {got.get(slot, 0)} != {want}")
        blobs = seg.blobs()
        for i in range(workers):
            b = blobs.get(i)
            if not b or not b.get("done") or b.get("progress") != iters:
                fail(f"worker {i} final blob wrong: {b!r}")

        # Crash reclaim: reaping worker 0 returns its residue and the
        # merged totals drop by exactly that much.
        reclaimed = seg.reap(0)
        if reclaimed.get("held") != 1:
            fail(f"reap reclaimed {reclaimed} (held != 1)")
        totals = seg.totals()
        if totals.get("held") != held_want - 1:
            fail(f"post-reap held {totals.get('held')} != {held_want - 1}")
        if 0 in seg.live():
            fail("worker 0 still live after reap")
    finally:
        stop_readers.set()
        for p in procs:
            if p.poll() is None:
                p.kill()
        seg.close(unlink=True)
    return errors


def hammer_shm_journeys(workers: int = 4, iters: int = 3000,
                        reader_threads: int = 3) -> list[str]:
    """Multi-PROCESS hammer for the seqlocked journey slots (ISSUE 18).

    N child processes (``python -m inference_gateway_tpu.cluster.shm
    --hammer-journey``) rewrite their 4 journey slots ``iters`` times
    with variable-length self-checking payloads (``check == len(pad) +
    n``) while parent reader threads spin ``read_journey`` /
    ``journey_records`` / ``find_journeys`` mid-storm. A torn read —
    bytes from two different writes — either breaks JSON (the seqlock
    retry loop hides transient tears; 8 straight tears return None,
    which is legal) or, the dangerous case, DECODES but mixes payloads:
    the embedded checksum and the worker echo catch exactly that.

    At quiesce: every slot holds its writer's LAST payload (slot 0 the
    ``done`` stamp), lookups find the expected trace ids, and — the
    survival contract the chaos e2e depends on — ``reap()`` +
    ``begin_generation()`` leave every journey record readable.
    """
    import os
    import subprocess
    import sys
    import uuid

    from inference_gateway_tpu.cluster.shm import ClusterSegment

    errors: list[str] = []
    errors_lock = threading.Lock()

    def fail(msg: str) -> None:
        with errors_lock:
            errors.append(f"{msg} [thread={threading.current_thread().name}]")

    def check_record(rec: dict) -> None:
        """Integrity of one decoded journey payload; rec may legally be
        a worker's stub/done record (empty pad)."""
        if rec.get("check") != len(rec.get("pad", "")) + rec.get("n", -1):
            fail(f"torn journey payload (checksum): {rec!r}")
        w = rec.get("w")
        if not isinstance(w, int) or not 0 <= w < workers:
            fail(f"torn journey payload (worker echo): {rec!r}")
        elif not str(rec.get("trace_id", "")).startswith(f"t-{w}-"):
            fail(f"journey trace id from another slab: {rec!r}")

    name = f"ig-jhammer-{os.getpid()}-{uuid.uuid4().hex[:6]}"
    seg = ClusterSegment.create(name, workers=workers,
                                counters=("held", "ops"), tenant_slots=8,
                                blob_cap=1024, journey_slots=4,
                                journey_slot_bytes=512)
    procs: list["subprocess.Popen[bytes]"] = []
    stop_readers = threading.Event()
    try:
        for i in range(workers):
            seg.begin_generation(i, i + 1)
        for i in range(workers):
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "inference_gateway_tpu.cluster.shm",
                 "--hammer-journey", name, str(workers), str(i), str(iters)],
                cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

        def reader(tid: int) -> None:
            n = 0
            while not stop_readers.is_set():
                try:
                    if n % 3 == 0:
                        for rec in seg.journey_records():
                            check_record(rec)
                            if rec["worker"] != rec["w"]:
                                fail(f"record annotated with wrong slab: {rec!r}")
                    elif n % 3 == 1:
                        rec = seg.read_journey(n % workers, (n // workers) % 4)
                        if rec is not None:
                            check_record(rec)
                    else:
                        for rec in seg.find_journeys(f"t-{tid % workers}-1"):
                            check_record(rec)
                    n += 1
                except Exception as e:
                    fail(f"reader: {e!r}")
                    return

        readers = [threading.Thread(target=reader, args=(t,),
                                    name=f"jshm-r{t}", daemon=True)
                   for t in range(reader_threads)]
        for t in readers:
            t.start()
        for i, p in enumerate(procs):
            try:
                rc = p.wait(timeout=120)
            except subprocess.TimeoutExpired:
                p.kill()
                fail(f"worker {i} hung")
                continue
            if rc != 0:
                fail(f"worker {i} exited {rc}")
        stop_readers.set()
        for t in readers:
            t.join(timeout=30)
            if t.is_alive():
                fail(f"{t.name} did not finish")
        if errors:
            return errors

        # Quiesce: each worker's slot 0 holds the done stamp; slots 1-3
        # hold the LAST write for that slot (check still consistent).
        for i in range(workers):
            done = seg.read_journey(i, 0)
            if not done or not done.get("done") or done.get("n") != iters:
                fail(f"worker {i} slot 0 final record wrong: {done!r}")
            for slot in range(1, 4):
                rec = seg.read_journey(i, slot)
                if rec is None:
                    fail(f"worker {i} slot {slot} empty at quiesce")
                else:
                    check_record(rec)
            found = seg.find_journeys(f"t-{i}-1")
            if len(found) != 1 or found[0].get("w") != i:
                fail(f"find_journeys(t-{i}-1) -> {found!r}")

        # THE survival contract: reap + a fresh generation must leave
        # the dead worker's journey ring readable (the chaos e2e reads a
        # SIGKILLed worker's half of a journey through exactly this).
        seg.reap(0)
        if seg.read_journey(0, 0) is None:
            fail("journey slot lost to reap()")
        seg.begin_generation(0, workers + 1)
        rec = seg.read_journey(0, 1)
        if rec is None:
            fail("journey slot lost to begin_generation()")
        else:
            check_record(rec)
        if not seg.find_journeys("t-0-2"):
            fail("find_journeys lost the dead worker's records")
    finally:
        stop_readers.set()
        for p in procs:
            if p.poll() is None:
                p.kill()
        seg.close(unlink=True)
    return errors


def hammer_compile_ledger(writer_threads: int = 6, reader_threads: int = 2,
                          iters: int = 300) -> list[str]:
    """Concurrency hammer for the ISSUE 19 ``CompileLedger``.

    The ledger is written from every wrapped jit entry point — prefill
    and decode seams run on the scheduler thread, warmup on an executor
    thread — while ``/debug/compile`` snapshots and the scheduler's
    recompile-count reads land from the serving thread, and a
    supervised restart flips the warmup bracket mid-flight. N writer
    threads drive wrapped functions with thread-unique signatures (the
    fallback signature detector path — deterministic compile counting),
    a flipper toggles warmup_begin/mark_warmup_complete, and readers
    snapshot concurrently. Returns error strings; empty means no
    exceptions, no torn snapshot, and exactly-conserved compile counts.
    """
    from inference_gateway_tpu.otel.device_observatory import CompileLedger

    ledger = CompileLedger(size=64)
    errors: list[str] = []
    errors_lock = threading.Lock()
    barrier = threading.Barrier(writer_threads + reader_threads + 1)
    done = threading.Event()

    def fail(msg: str) -> None:
        with errors_lock:
            errors.append(f"{msg} [thread={threading.current_thread().name}]")

    def base_fn(tag):
        return tag

    wrapped = {t: ledger.wrap(f"prog_{t % 3}", base_fn)
               for t in range(writer_threads)}

    def writer(tid: int) -> None:
        barrier.wait()
        fn = wrapped[tid]
        for i in range(iters):
            try:
                # Thread-unique signature per call: every call is a
                # first-seen signature, so total compiles is exact.
                fn(f"w{tid}-{i}")
            except Exception as e:
                fail(f"writer: {e!r}")
                return

    def flipper() -> None:
        barrier.wait()
        while not done.is_set():
            try:
                ledger.mark_warmup_complete()
                ledger.warmup_begin()
            except Exception as e:
                fail(f"flipper: {e!r}")
                return

    def reader() -> None:
        barrier.wait()
        while not done.is_set():
            try:
                snap = ledger.snapshot()
                if snap["recompiles"] > snap["compiles"]:
                    fail(f"torn snapshot: recompiles {snap['recompiles']} > "
                         f"compiles {snap['compiles']}")
                    return
                if len(snap["records"]) > 64:
                    fail(f"ring overflow: {len(snap['records'])} records")
                    return
                for rec in snap["records"]:
                    if "program" not in rec or "signature" not in rec:
                        fail(f"torn record: {rec}")
                        return
                ledger.recompile_count()
                ledger.recent_recompiles(5)
            except Exception as e:
                fail(f"reader: {e!r}")
                return

    threads = [threading.Thread(target=writer, args=(t,), name=f"ledger-w{t}",
                                daemon=True)
               for t in range(writer_threads)]
    threads += [threading.Thread(target=reader, name=f"ledger-r{t}", daemon=True)
                for t in range(reader_threads)]
    flip = threading.Thread(target=flipper, name="ledger-flip", daemon=True)
    for t in threads:
        t.start()
    flip.start()
    for t in threads[:writer_threads]:
        t.join(timeout=120)
        if t.is_alive():
            fail(f"{t.name} did not finish")
    done.set()
    for t in threads[writer_threads:]:
        t.join(timeout=120)
        if t.is_alive():
            fail(f"{t.name} did not finish")
    flip.join(timeout=120)
    expected = writer_threads * iters
    if ledger.compiles != expected:
        fail(f"compile count lost updates: {ledger.compiles} != {expected}")
    snap = ledger.snapshot()
    if snap["recompiles"] != ledger.recompiles:
        fail("snapshot/counter recompile divergence")
    return errors
