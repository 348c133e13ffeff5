"""Performance introspection (ISSUE 4 tentpole).

PR 3 made the gateway *report* latency; this module explains *where the
time went* when a number regresses, with four cooperating pieces:

- ``SamplingProfiler`` — a wall-clock sampling profiler over
  ``sys._current_frames()``: a daemon thread samples every live thread at
  a configurable Hz and aggregates into bounded collapsed-stack counts
  (flamegraph.pl / speedscope input format). Two modes share the core:
  on-demand capture (``GET /debug/profile?seconds=N&hz=M``) and an
  always-on continuous mode keeping a ring of recent windows.
- ``EventLoopWatchdog`` — asyncio scheduling-lag heartbeat. The relay
  hot path lives and dies on loop latency (a CPU relay run read 58k
  chunks/s at 128 streams vs 84k at 32); the heartbeat measures how late the loop
  woke it into the ``eventloop.lag`` histogram, and lag beyond the
  threshold is a *stall*: counted, wide-evented through the access-log
  sink with the loop thread's stack. A companion daemon thread snapshots
  that stack WHILE the loop is wedged — the heartbeat itself can only
  run after the stall ended, so without the thread every stall event
  would name the watchdog's own frame.
- ``StepTimeline`` — bounded ring of engine step records (wall time,
  prefill/decode/spec kind, batch occupancy, tokens emitted, KV
  utilization) written by the scheduler thread and served at
  ``GET /debug/timeline``; each record also lands in the
  ``engine.step_duration`` histogram. It is also the scheduler
  thread's span recorder (ISSUE 24): a record carries its start on the
  span clock (``span_clock_ns``), its host phases (``phase_span``
  stamps a phase and opens the same-named ``TraceAnnotation``
  together), and how long the device was starved before it and by
  which cause (``CauseCursor``, ISSUE 38).
- ``SlowRequestLog`` — requests breaching configurable TTFT/TPOT/total
  thresholds get their phase clock, trace id, and the surrounding
  engine-step window captured into a bounded log surfaced in
  ``/debug/status``.

Everything is zero-overhead-when-off (no thread, no task, a single
``is None`` check on the hot paths) and testable with zero real sleeps:
the watchdog takes the PR 1 clock, the profiler/timeline/slow-log are
plain data structures driven by the caller.
"""

from __future__ import annotations

import asyncio
import contextlib
import sys
import threading
import time
from collections import deque
from typing import Any, Callable

from inference_gateway_tpu.resilience.clock import MonotonicClock

# Aggregation bucket for stacks beyond the per-window unique-stack bound:
# the profiler's memory is O(max_stacks), never O(distinct stacks).
OVERFLOW_STACK = "__overflow__"

# /debug/profile guard rails: a capture blocks one executor thread.
MAX_CAPTURE_SECONDS = 60.0
MAX_CAPTURE_HZ = 1000.0

# The span clock (ISSUE 24): one anchor pair a process, taken when this
# module loads (before any scheduler starts), so every scheduler a
# supervised restart builds, the start-up stamps and jax_trace_capture
# read ONE clock. Epoch nanoseconds that advance with perf_counter_ns:
# monotonic, and comparable with GenRequest.phase_ns and a client's epoch.
_ANCHOR_EPOCH_NS, _ANCHOR_PERF_NS = time.time_ns(), time.perf_counter_ns()


def span_clock_ns(perf_ns: int | None = None) -> int:
    """Epoch ns of ``perf_ns`` (a ``time.perf_counter_ns()`` reading; now
    when omitted) on the span clock."""
    if perf_ns is None:
        perf_ns = time.perf_counter_ns()
    return _ANCHOR_EPOCH_NS + (perf_ns - _ANCHOR_PERF_NS)


_NO_SPAN = contextlib.nullcontext()
_annotation: Callable[..., Any] | None = None


def trace_annotation(name: str, **stats: Any):
    """``jax.profiler.TraceAnnotation(name, **stats)``: a host span in the
    profiler's own trace while a capture runs, next to nothing otherwise.
    A process without JAX (the gateway) gets a null context."""
    global _annotation
    if _annotation is None:
        try:
            from jax.profiler import TraceAnnotation
            _annotation = TraceAnnotation
        except Exception:
            _annotation = lambda *_a, **_k: _NO_SPAN  # noqa: E731
    return _annotation(name, **stats)


def perf_ns_of(epoch_ns: int) -> int:
    """The ``perf_counter_ns`` reading at which the span clock read
    ``epoch_ns``: ``span_clock_ns`` backwards, for a stamp taken with
    ``time.time_ns`` (a request's ``phase_ns``)."""
    return _ANCHOR_PERF_NS + (epoch_ns - _ANCHOR_EPOCH_NS)


# The idle ledger's closed list of causes (ISSUE 38; docs/observability.md
# says what code each covers): the last word of every span the scheduler
# thread opens, the lock's wait, and "loop" for what lies under none.
IDLE_CAUSES = ("wake", "rows", "prep", "register_slot", "drain", "build", "lock_wait", "pages",
               "assemble", "upload_dispatch", "deliver", "fetch", "fetch_copy", "emit", "release",
               "release_slot", "record", "loop")


class CauseCursor:
    """The idle ledger's one primitive (ISSUE 38): which cause the
    scheduler thread is in, and while the starvation clock runs, how long
    it has been in each. A cause is the last word of a span's name
    (``sched.emit`` -> ``emit``); spans nest and the innermost open one is
    charged; with none open the thread is in ``loop``, the remainder that
    is reported and never folded into a neighbour. ``start`` opens the
    ledger at a ``perf_counter_ns`` reading (the fetch's return, or an
    arrival's ``submit`` stamp: it may lie in the past), ``stop`` charges
    up to its reading and hands the ledger over, ns by cause: it sums to
    ``stop - start`` exactly, because every charge runs from the last mark
    to the next. While the clock does not run a mark moves the stack and
    charges nothing. Owned by one scheduler and touched by its thread
    alone; ``phase_span`` and ``cause_span`` find it through
    ``bind_cursor``."""

    __slots__ = ("_stack", "_by", "_mark")

    def __init__(self) -> None:
        self._stack: list[str] = []
        self._by: dict[str, int] | None = None
        self._mark = 0

    def _charge(self, now: int) -> None:
        by = self._by
        if by is not None and now > self._mark:
            cause = self._stack[-1] if self._stack else "loop"
            by[cause] = by.get(cause, 0) + now - self._mark
            self._mark = now

    def enter(self, cause: str, now: int) -> None:
        self._charge(now)
        self._stack.append(cause)

    def exit(self, now: int) -> None:
        self._charge(now)
        self._stack.pop()

    def start(self, t0: int) -> None:
        self._by, self._mark = {}, t0

    def stop(self, now: int) -> dict[str, int]:
        self._charge(now)
        by, self._by = self._by, None
        return by or {}


_thread = threading.local()  # .cursor: the CauseCursor of the scheduler this thread runs


def bind_cursor(cursor: CauseCursor | None) -> None:
    """Make ``cursor`` the calling thread's (a scheduler's ``run`` binds
    its own while an observer is attached, None otherwise): from then on
    every ``phase_span`` / ``cause_span`` / ``stamp_since`` on this thread
    marks it."""
    _thread.cursor = cursor


class _PhaseSpan:
    __slots__ = ("_phases", "_key", "_ann", "_t0", "_cursor", "_cause")

    def __init__(self, phases: dict[str, float] | None, key: str | None, name: str) -> None:
        self._phases, self._key, self._ann = phases, key, trace_annotation(name)
        self._cursor = getattr(_thread, "cursor", None)
        self._cause = name.rpartition(".")[2]

    def __enter__(self) -> None:
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        if self._cursor is not None:
            self._cursor.enter(self._cause, self._t0)

    def __exit__(self, *exc: Any) -> None:
        now = time.perf_counter_ns()
        if self._phases is not None:
            self._phases[self._key] = self._phases.get(self._key, 0.0) + (now - self._t0) / 1e6
        if self._cursor is not None:
            self._cursor.exit(now)
        self._ann.__exit__(*exc)


def stamp_since(phases: dict[str, float] | None, key: str, t0_ns: int) -> None:
    """``phases[key]`` = milliseconds since ``t0_ns`` (a ``perf_counter_ns``
    reading), for a stretch no ``with`` block can wrap (a lock's wait); the
    thread's cause cursor is charged the same stretch under ``key``."""
    if phases is not None:
        now = time.perf_counter_ns()
        phases[key] = (now - t0_ns) / 1e6
        cursor = getattr(_thread, "cursor", None)
        if cursor is not None:
            cursor.enter(key, t0_ns)
            cursor.exit(now)


def phase_span(phases: dict[str, float] | None, key: str, name: str):
    """One phase of a step on the scheduler thread: adds the elapsed
    milliseconds to ``phases[key]`` and wraps the same stretch in a
    ``TraceAnnotation(name)``, so the timeline record and the device trace
    cannot drift apart; the thread's cause cursor (``bind_cursor``) is
    marked on the way in and out, under the last word of ``name``.
    ``phases`` None (no observer attached) stamps nothing and opens
    nothing."""
    return _NO_SPAN if phases is None else _PhaseSpan(phases, key, name)


def cause_span(name: str):
    """A cause of the idle ledger that is no phase of a record (a finish's
    release, the instruments' own writes, the wait for an arrival): the
    same ``TraceAnnotation(name)`` and the same marks on the thread's cause
    cursor as ``phase_span``, and no stamp. A thread with no cursor bound
    (no observer attached, or not a scheduler's) opens nothing."""
    return _NO_SPAN if getattr(_thread, "cursor", None) is None else _PhaseSpan(None, None, name)


class CaptureBusyError(RuntimeError):
    """An on-demand capture is already running on this profiler. The
    metrics listener is unauthenticated, so without this guard N
    concurrent 60s /debug/profile requests would pin N threads of the
    process-wide default executor — starving DNS lookups and every other
    run_in_executor user for a minute."""


def _format_stack(frame, thread_name: str, max_depth: int = 64) -> str:
    """One sample in collapsed form: ``thread:NAME;root;...;leaf`` with
    ``pkg/file.py:func`` frame labels (greppable, flamegraph-ready)."""
    frames: list[str] = []
    depth = 0
    while frame is not None and depth < max_depth:
        code = frame.f_code
        fname = code.co_filename.replace("\\", "/")
        short = "/".join(fname.rsplit("/", 2)[-2:])
        frames.append(f"{short}:{code.co_name}")
        frame = frame.f_back
        depth += 1
    frames.append(f"thread:{thread_name}")
    frames.reverse()
    return ";".join(frames)


class StackWindow:
    """Bounded collapsed-stack counts for one sampling window."""

    __slots__ = ("started", "samples", "counts", "max_stacks")

    def __init__(self, max_stacks: int) -> None:
        self.started = time.time()
        self.samples = 0
        self.counts: dict[str, int] = {}
        self.max_stacks = max_stacks

    def add(self, stack: str) -> None:
        counts = self.counts
        if stack in counts:
            counts[stack] += 1
        elif len(counts) < self.max_stacks:
            counts[stack] = 1
        else:
            counts[OVERFLOW_STACK] = counts.get(OVERFLOW_STACK, 0) + 1
        self.samples += 1


def render_collapsed(counts: dict[str, int]) -> str:
    """flamegraph.pl / speedscope input: one ``stack count`` line per
    distinct stack, hottest first."""
    lines = [f"{stack} {n}" for stack, n in
             sorted(counts.items(), key=lambda kv: (-kv[1], kv[0]))]
    return "\n".join(lines) + ("\n" if lines else "")


class SamplingProfiler:
    """Wall-clock sampling profiler over ``sys._current_frames()``.

    ``profile(seconds, hz)`` is the blocking on-demand core (run it via
    ``capture`` from async handlers so the event loop — one of the
    profiled threads — keeps serving). ``start_continuous()`` spawns a
    daemon thread sampling at ``hz`` into the current window, rotating
    into a bounded ring every ``window_s`` seconds; ``snapshot()`` merges
    the ring for flamegraph-over-the-last-N-minutes queries. Lifecycle is
    lock-guarded and idempotent so concurrent start/sample/stop (the
    race-harness hammer) cannot leak threads or tear windows.
    """

    def __init__(self, hz: float = 29.0, window_s: float = 10.0, windows: int = 6,
                 max_stacks: int = 2048, logger=None) -> None:
        self.hz = max(float(hz), 0.1)
        self.window_s = max(float(window_s), 0.1)
        self.max_stacks = max(int(max_stacks), 16)
        self.logger = logger
        self._ring: deque[StackWindow] = deque(maxlen=max(int(windows), 1))
        self._current: StackWindow | None = None
        self._lock = threading.Lock()
        self._thread: threading.Thread | None = None
        self._stop = threading.Event()
        # At most ONE on-demand capture per profiler occupies the shared
        # default executor (CaptureBusyError above).
        self._capture_busy = threading.Lock()

    # -- sampling core -------------------------------------------------
    @staticmethod
    def sample_into(window: StackWindow, exclude: frozenset[int] = frozenset()) -> None:
        """One sample of every live thread except ``exclude`` (a sampler
        must not profile itself into the hottest stack)."""
        names = {t.ident: t.name for t in threading.enumerate()}
        for tid, frame in sys._current_frames().items():
            if tid in exclude:
                continue
            window.add(_format_stack(frame, names.get(tid, f"tid-{tid}")))

    def profile(self, seconds: float, hz: float | None = None) -> StackWindow:
        """Blocking on-demand capture into a fresh window."""
        seconds = min(max(float(seconds), 0.01), MAX_CAPTURE_SECONDS)
        hz = min(max(float(hz if hz is not None else self.hz), 0.1), MAX_CAPTURE_HZ)
        window = StackWindow(self.max_stacks)
        me = frozenset((threading.get_ident(),))
        period = 1.0 / hz
        deadline = time.monotonic() + seconds
        next_t = time.monotonic()
        while True:
            self.sample_into(window, exclude=me)
            next_t += period
            now = time.monotonic()
            if now >= deadline:
                return window
            if next_t > now:
                time.sleep(min(next_t, deadline) - now)

    async def capture(self, seconds: float, hz: float | None = None) -> StackWindow:
        """On-demand capture off-loop, so the profiled event loop keeps
        running (and shows up in its own profile). Raises
        ``CaptureBusyError`` when a capture is already in flight."""
        if not self._capture_busy.acquire(blocking=False):
            raise CaptureBusyError("a profile capture is already running")
        try:
            return await asyncio.get_running_loop().run_in_executor(
                None, self.profile, seconds, hz)
        finally:
            self._capture_busy.release()

    # -- continuous mode -----------------------------------------------
    def start_continuous(self) -> None:
        with self._lock:
            if self._thread is not None and self._thread.is_alive():
                return
            self._stop = threading.Event()
            self._current = StackWindow(self.max_stacks)
            self._thread = threading.Thread(
                target=self._run, name="profiler-sampler", daemon=True)
            self._thread.start()

    def stop(self) -> None:
        with self._lock:
            thread = self._thread
            self._stop.set()
        if thread is not None:
            thread.join(timeout=5.0)
        with self._lock:
            if self._thread is thread:
                self._thread = None
                if self._current is not None and self._current.samples:
                    self._ring.append(self._current)
                self._current = None

    def _run(self) -> None:
        stop = self._stop
        me = frozenset((threading.get_ident(),))
        period = 1.0 / self.hz
        rotate_at = time.monotonic() + self.window_s
        while not stop.wait(period):
            try:
                with self._lock:
                    window = self._current
                    if window is None:
                        break
                    if time.monotonic() >= rotate_at:
                        if window.samples:
                            self._ring.append(window)
                        window = self._current = StackWindow(self.max_stacks)
                        rotate_at = time.monotonic() + self.window_s
                self.sample_into(window, exclude=me)
            except Exception as e:  # pragma: no cover - defensive
                if self.logger is not None:
                    self.logger.error("profiler sample failed", e)

    @property
    def continuous(self) -> bool:
        thread = self._thread
        return thread is not None and thread.is_alive()

    def snapshot(self) -> dict[str, int]:
        """Merged collapsed-stack counts over the ring + current window.

        The live window is copied with ``dict()`` (GIL-atomic in C)
        before merging — Python-level iteration over a dict the sampler
        thread is concurrently inserting into would raise."""
        with self._lock:
            counts_list = [dict(w.counts) for w in self._ring]
            if self._current is not None:
                counts_list.append(dict(self._current.counts))
        merged: dict[str, int] = {}
        for counts in counts_list:
            for stack, n in counts.items():
                merged[stack] = merged.get(stack, 0) + n
        return merged

    def stats(self) -> dict[str, Any]:
        with self._lock:
            windows = list(self._ring)
            current = self._current
        samples = sum(w.samples for w in windows) + (current.samples if current else 0)
        return {
            "continuous": self.continuous,
            "hz": self.hz,
            "window_seconds": self.window_s,
            "windows_retained": len(windows) + (1 if current else 0),
            "samples": samples,
        }


async def handle_profile_query(profiler: SamplingProfiler | None, *, seconds: str = "",
                               hz: str = "", mode: str = "") -> tuple[int, str, str]:
    """Shared ``/debug/profile`` logic for the metrics listener and the
    sidecar: returns ``(status, content_type, body)`` so neither endpoint
    layer imports the other's Response type."""
    if profiler is None:
        return (404, "application/json",
                '{"error": "profiling disabled (TELEMETRY_PROFILING_ENABLE)"}')
    if mode == "continuous":
        counts = profiler.snapshot()
        if not counts:
            return (404, "application/json",
                    '{"error": "no continuous profile yet (TELEMETRY_PROFILING_CONTINUOUS)"}')
        return (200, "text/plain; charset=utf-8", render_collapsed(counts))
    try:
        secs = float(seconds) if seconds else 1.0
        rate = float(hz) if hz else profiler.hz
    except ValueError:
        return (400, "application/json", '{"error": "seconds and hz must be numbers"}')
    try:
        window = await profiler.capture(secs, rate)
    except CaptureBusyError:
        return (409, "application/json",
                '{"error": "a profile capture is already running; retry when it finishes"}')
    return (200, "text/plain; charset=utf-8", render_collapsed(window.counts))


# ---------------------------------------------------------------------------
# Event-loop stall watchdog
# ---------------------------------------------------------------------------
class EventLoopWatchdog:
    """Asyncio scheduling-lag heartbeat with mid-stall stack capture.

    The heartbeat coroutine sleeps ``interval`` on the injected clock and
    records how late the loop woke it into ``eventloop.lag``; lag beyond
    ``threshold`` increments ``eventloop.stall`` and emits one wide event
    through the access-log sink (falling back to the logger) carrying the
    lag, the loop thread's stack, and any registered context probes
    (e.g. live connection counts). With the production clock a companion
    daemon thread watches the heartbeat timestamps and snapshots the
    loop thread's stack while it is actually wedged; with a VirtualClock
    (tests) the thread stays off and the whole state machine runs with
    zero real sleeps.
    """

    def __init__(self, otel=None, access_log=None, interval: float = 0.25,
                 threshold: float = 0.1, clock=None, source: str = "gateway",
                 logger=None) -> None:
        self.otel = otel
        self.access_log = access_log
        self.interval = max(float(interval), 0.01)
        self.threshold = max(float(threshold), 0.001)
        self.clock = clock or MonotonicClock()
        self.source = source
        self.logger = logger
        self.stalls = 0
        self.beats = 0
        self.last_lag = 0.0
        self.last_stall: dict[str, Any] | None = None
        self._probes: list[tuple[str, Callable[[], Any]]] = []
        self._task: asyncio.Task | None = None
        self._thread: threading.Thread | None = None
        self._thread_stop = threading.Event()
        self._loop_tid: int | None = None
        self._beat_wall = time.monotonic()
        # (captured_at_wall, collapsed_stack) written by the snapshot
        # thread while the loop is wedged, consumed by the next beat.
        self._pending_stack: tuple[float, str] | None = None

    def add_context(self, name: str, probe: Callable[[], Any]) -> None:
        """Attach a forensic probe (e.g. a server's connection count)
        whose value is stamped onto every stall event."""
        self._probes.append((name, probe))

    # -- lifecycle -----------------------------------------------------
    def start(self) -> None:
        if self._task is not None and not self._task.done():
            return
        self._task = asyncio.get_running_loop().create_task(
            self._heartbeat(), name="eventloop-watchdog")
        if isinstance(self.clock, MonotonicClock):
            self._thread_stop = threading.Event()
            self._thread = threading.Thread(
                target=self._watch, name="watchdog-sampler", daemon=True)
            self._thread.start()

    async def stop(self) -> None:
        self._thread_stop.set()
        if self._task is not None:
            self._task.cancel()
            try:
                await self._task
            except (asyncio.CancelledError, Exception):
                pass
            self._task = None
        thread = self._thread
        if thread is not None:
            # join() would block the loop; the thread polls stop_event at
            # interval/2 cadence and is a daemon — detach and let it exit.
            self._thread = None

    # -- heartbeat (on the watched loop) -------------------------------
    async def _heartbeat(self) -> None:
        self._loop_tid = threading.get_ident()
        while True:
            t0 = self.clock.now()
            self._beat_wall = time.monotonic()
            await self.clock.sleep(self.interval)
            lag = max(self.clock.now() - t0 - self.interval, 0.0)
            self.beats += 1
            self.last_lag = lag
            if self.otel is not None:
                self.otel.record_eventloop_lag(self.source, lag)
            if lag > self.threshold:
                self._on_stall(lag)

    def _on_stall(self, lag: float) -> None:
        self.stalls += 1
        if self.otel is not None:
            self.otel.record_eventloop_stall(self.source)
        stack = None
        pending, self._pending_stack = self._pending_stack, None
        if pending is not None and pending[0] >= self._beat_wall:
            stack = pending[1]  # captured while the loop was wedged
        event: dict[str, Any] = {
            "log": "stall",
            "kind": "eventloop.stall",
            "source": self.source,
            "lag_s": round(lag, 4),
            "threshold_s": self.threshold,
            "stack": stack,
        }
        for name, probe in self._probes:
            try:
                event[name] = probe()
            except Exception:
                event[name] = None
        self.last_stall = event
        if self.access_log is not None:
            self.access_log.emit(event)
        elif self.logger is not None:
            self.logger.warn("event loop stall", "lag_s", round(lag, 4),
                             "stack", stack or "<missed>")

    # -- mid-stall snapshots (companion thread, real clock only) -------
    def _watch(self) -> None:
        stop = self._thread_stop
        while not stop.wait(self.interval / 2):
            overdue = time.monotonic() - self._beat_wall
            if overdue <= self.interval + self.threshold:
                continue
            tid = self._loop_tid
            if tid is None:
                continue
            frame = sys._current_frames().get(tid)
            if frame is not None:
                self._pending_stack = (
                    time.monotonic(), _format_stack(frame, "event-loop"))

    def stats(self) -> dict[str, Any]:
        return {
            "watchdog": self._task is not None and not self._task.done(),
            "interval_s": self.interval,
            "threshold_s": self.threshold,
            "beats": self.beats,
            "stalls": self.stalls,
            "last_lag_s": round(self.last_lag, 4),
            "last_stall": self.last_stall,
        }


# ---------------------------------------------------------------------------
# Engine decode-step timeline
# ---------------------------------------------------------------------------
class StepTimeline:
    """Bounded ring of per-engine-step records written by the scheduler
    thread: what the batch was doing, step by step, when a latency number
    regressed. Readers (``/debug/timeline``, slow-request forensics)
    copy under the lock; the writer pays one dict + deque append per
    engine *chunk*, not per token."""

    def __init__(self, size: int = 512, otel=None, model: str = "") -> None:
        self._ring: deque[dict[str, Any]] = deque(maxlen=max(int(size), 8))
        self._lock = threading.Lock()
        self.otel = otel
        self.model = model
        self.steps = 0
        self.records = 0

    def record_host_gap(self, kind: str, gap_ms: float) -> None:
        """One host-gap observation (ISSUE 14): wall time the host spent
        between finishing its last device interaction and dispatching
        the next program — recorded per DISPATCH (decode chunks fresh or
        chained, mixed steps, prefills) into the engine.host_gap_ms
        histogram; the same gap rides that dispatch's step record."""
        if self.otel is not None:
            self.otel.record_host_gap(self.model, kind, gap_ms)

    def record(self, kind: str, duration_s: float, *, n_steps: int = 1, batch: int = 0,
               tokens: int = 0, kv_utilization: float = 0.0, queue_depth: int = 0,
               cost: dict[str, Any] | None = None,
               spans: dict[str, Any] | None = None) -> None:
        """``spans`` (ISSUE 24) are the scheduler thread's own stamps for
        this step, merged into the record as they are: ``phases`` (ms by
        phase), ``starved_ms`` / ``inflight_at_dispatch`` / ``host_gap_ms``
        of the step's dispatch and, where ``starved_ms`` > 0, ``starved_by``
        (ISSUE 38: the same milliseconds by cause, from the scheduler's
        ``CauseCursor``), ``context_tokens``, a decode chunk's
        ``submit_ts``, an admission's ``prep``. ``cost`` (ISSUE 19) is
        ``{"recompiled": n}`` on a step whose wall time includes n
        steady-state recompiles, else None."""
        end_ns = span_clock_ns()
        rec = {
            # Both on the span clock: the step's first host instruction
            # and the moment this record was written, epoch seconds.
            "start_ts": (end_ns - int(duration_s * 1e9)) / 1e9,
            "ts": end_ns / 1e9,
            "kind": kind,
            "duration_ms": round(duration_s * 1000, 3),
            "steps": n_steps,
            "batch": batch,
            "tokens": tokens,
            "kv_utilization": round(kv_utilization, 4),
            "queue_depth": queue_depth,
        }
        if spans:
            rec.update(spans)
        if cost:
            rec.update(cost)
        with self._lock:
            self._ring.append(rec)
            self.steps += n_steps
            self.records += 1
        if self.otel is not None:
            self.otel.record_engine_step(self.model, kind, duration_s)

    def tail(self, n: int | None = None) -> list[dict[str, Any]]:
        with self._lock:
            entries = list(self._ring)
        return entries[-n:] if n else entries

    def window(self, start_ts: float, end_ts: float, margin: float = 0.25) -> list[dict[str, Any]]:
        """Records overlapping [start_ts - margin, end_ts + margin]
        (epoch seconds) — the engine-step context around one request."""
        lo, hi = start_ts - margin, end_ts + margin
        with self._lock:
            return [r for r in self._ring if lo <= r["ts"] <= hi]

    def stats(self) -> dict[str, Any]:
        with self._lock:
            retained = len(self._ring)
            last = self._ring[-1] if retained else None
        return {"steps": self.steps, "records": self.records,
                "retained": retained, "last": last}


# ---------------------------------------------------------------------------
# Slow-request forensics
# ---------------------------------------------------------------------------
class SlowRequestLog:
    """Bounded log of requests that breached latency thresholds.

    Two feeders: the sidecar's ``observe_phases`` (scheduler phase clock
    in epoch ns, plus the surrounding engine-step window from an attached
    ``StepTimeline``) and the gateway edge's ``observe_event`` (an
    event dict shaped like the wide-event access log line — fed by the
    telemetry middleware's own per-request measurements, so forensics
    work with the access log off; an ``AccessLog`` can also be wired as
    a feeder). Thresholds of 0 disable that check; with all three at 0
    the log is inert.
    """

    def __init__(self, ttft_s: float = 0.0, tpot_s: float = 0.0, total_s: float = 0.0,
                 size: int = 64, timeline: StepTimeline | None = None,
                 otel=None, source: str = "gateway") -> None:
        self.ttft_s = max(float(ttft_s), 0.0)
        self.tpot_s = max(float(tpot_s), 0.0)
        self.total_s = max(float(total_s), 0.0)
        self.timeline = timeline
        self.otel = otel
        self.source = source
        self._ring: deque[dict[str, Any]] = deque(maxlen=max(int(size), 1))
        self._lock = threading.Lock()
        self.observed = 0
        self.breached = 0

    @property
    def enabled(self) -> bool:
        return bool(self.ttft_s or self.tpot_s or self.total_s)

    def _breaches(self, ttft: float | None, tpot: float | None,
                  total: float | None) -> list[str]:
        out = []
        if self.ttft_s and ttft is not None and ttft > self.ttft_s:
            out.append("ttft")
        if self.tpot_s and tpot is not None and tpot > self.tpot_s:
            out.append("tpot")
        if self.total_s and total is not None and total > self.total_s:
            out.append("total")
        return out

    def _append(self, rec: dict[str, Any], breaches: list[str]) -> None:
        rec["breach"] = breaches
        with self._lock:
            self._ring.append(rec)
            self.breached += 1
        if self.otel is not None:
            for b in breaches:
                self.otel.record_slow_request(self.source, b)

    def observe_phases(self, *, request_id: str, trace_id: str, model: str,
                       phase_ns: dict[str, int], output_tokens: int,
                       stream: bool, finish_reason: str | None) -> dict[str, Any] | None:
        """Sidecar feeder: judge one finished request by its phase clock;
        on breach capture the clock, the trace id, and the engine-step
        window the request decoded inside."""
        if not self.enabled:
            return None
        self.observed += 1
        submit, admit = phase_ns.get("submit"), phase_ns.get("admit")
        first, finish = phase_ns.get("first_token"), phase_ns.get("finish")
        ttft = (first - submit) / 1e9 if submit is not None and first is not None else None
        total = (finish - submit) / 1e9 if submit is not None and finish is not None else None
        tpot = None
        if first is not None and finish is not None and output_tokens > 1:
            tpot = (finish - first) / 1e9 / (output_tokens - 1)
        breaches = self._breaches(ttft, tpot, total)
        if not breaches:
            return None
        to_ms = lambda a, b: round((b - a) / 1e6, 3) if a is not None and b is not None else None
        rec: dict[str, Any] = {
            "ts": time.time(),
            "source": self.source,
            "request_id": request_id,
            "trace_id": trace_id or None,
            "model": model,
            "stream": stream,
            "finish_reason": finish_reason,
            "output_tokens": output_tokens,
            "ttft_ms": to_ms(submit, first),
            "total_ms": to_ms(submit, finish),
            "tpot_ms": round(tpot * 1000, 3) if tpot is not None else None,
            "phases_ms": {
                "queue_wait": to_ms(submit, admit),
                "prefill": to_ms(admit, first),
                "decode": to_ms(first, finish),
            },
        }
        if self.timeline is not None and submit is not None:
            end = finish or first or submit
            rec["engine_steps"] = self.timeline.window(submit / 1e9, end / 1e9)
        self._append(rec, breaches)
        return rec

    def observe_event(self, event: dict[str, Any]) -> dict[str, Any] | None:
        """Gateway-edge feeder: judge the wide event the access log just
        emitted (TTFC as the edge TTFT view, duration as total, derived
        per-token gap as TPOT)."""
        if not self.enabled or event.get("kind") == "eventloop.stall":
            return None
        self.observed += 1
        ttfc_ms = event.get("ttfc_ms")
        duration_ms = event.get("duration_ms")
        ttft = ttfc_ms / 1000 if isinstance(ttfc_ms, (int, float)) else None
        total = duration_ms / 1000 if isinstance(duration_ms, (int, float)) else None
        tpot = None
        tps = event.get("tokens_per_sec")
        if isinstance(tps, (int, float)) and tps > 0:
            tpot = 1.0 / tps
        breaches = self._breaches(ttft, tpot, total)
        if not breaches:
            return None
        rec = {
            "ts": time.time(),
            "source": self.source,
            "request_id": event.get("request_id"),
            "trace_id": event.get("trace_id"),
            "model": event.get("model"),
            "route": event.get("route"),
            "status": event.get("status"),
            "stream": event.get("stream"),
            "output_tokens": event.get("output_tokens"),
            "ttft_ms": ttfc_ms,
            "total_ms": duration_ms,
            "tpot_ms": round(tpot * 1000, 3) if tpot is not None else None,
        }
        self._append(rec, breaches)
        return rec

    def snapshot(self) -> dict[str, Any]:
        with self._lock:
            entries = list(self._ring)
        return {
            "thresholds": {"ttft_s": self.ttft_s, "tpot_s": self.tpot_s,
                           "total_s": self.total_s},
            "observed": self.observed,
            "breached": self.breached,
            "entries": entries,
        }


# ---------------------------------------------------------------------------
# Guarded device-trace capture
# ---------------------------------------------------------------------------
def jax_trace_capture(log_dir: str, seconds: float = 2.0, python_tracer: bool = True) -> dict[str, Any]:
    """Record a ``jax.profiler`` device trace into ``log_dir`` when a TPU
    backend is live; a harmless no-op (with the reason) anywhere else.
    Blocking — call via an executor from async handlers.

    The reply places the capture on the span clock: ``start_epoch_ns``
    immediately before ``start_trace``, ``stop_epoch_ns`` after
    ``stop_trace`` returned (it gathers and writes for seconds), and
    between them ``capture_epoch_ns``, the stretch ``start_trace`` returned
    → ``stop_trace`` called, which is when the device was being traced. The
    capture opens with a ``clock.sync`` annotation whose ``epoch_ns`` is
    the span clock at that moment, so the offset between the profiler's
    zero and this clock can be read off the trace itself.
    ``python_tracer=False`` turns the profiler's Python tracer off: the
    program's own annotations stay, at a fraction of the cost."""
    try:
        import jax

        platform = jax.devices()[0].platform
    except Exception as e:
        return {"captured": False, "reason": f"jax unavailable: {e}"}
    if platform != "tpu":
        return {"captured": False, "reason": f"device platform {platform!r} is not tpu"}
    try:
        import jax.profiler

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 1 if python_tracer else 0
        start_ns = span_clock_ns()
        jax.profiler.start_trace(log_dir, profiler_options=options)
        began_ns = span_clock_ns()
        with trace_annotation("clock.sync", epoch_ns=began_ns):
            pass
        time.sleep(min(max(float(seconds), 0.1), MAX_CAPTURE_SECONDS))
        ending_ns = span_clock_ns()
        jax.profiler.stop_trace()
        stop_ns = span_clock_ns()
    except Exception as e:
        return {"captured": False, "reason": f"trace failed: {e}"}
    return {"captured": True, "log_dir": log_dir, "seconds": seconds,
            "start_epoch_ns": start_ns, "stop_epoch_ns": stop_ns,
            "capture_epoch_ns": [began_ns, ending_ns], "python_tracer": bool(python_tracer)}
