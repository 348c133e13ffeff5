"""Continuous-batching scheduler.

The host-side serving loop (SURVEY.md §7 stage 5): admits waiting
requests into free cache slots (batched, bucket-padded prefill), then
advances every active slot one token per engine step, streaming tokens to
per-request callbacks as they are sampled. Runs on its own thread; the
asyncio server hands results back to clients via thread-safe queues.

Finish conditions: eos/stop tokens, per-request max_tokens, or cache-row
exhaustion (finish_reason "length").
"""

from __future__ import annotations

import itertools
import queue
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable

import numpy as np

from inference_gateway_tpu.otel.profiling import (
    CauseCursor, bind_cursor, cause_span, perf_ns_of, phase_span, span_clock_ns)
from inference_gateway_tpu.serving.engine import Engine, STOP_TABLE_WIDTH, build_stop_row
from inference_gateway_tpu.serving.kv_cache import OutOfPagesError

# Callback payload: (token_id, logprob, finished, finish_reason)
TokenCallback = Callable[[int, float, bool, str | None], None]

# engine.wasted_tokens{reason}: tokens the engine computed that no client
# will see, by why (Scheduler._wasted counts them).
WASTE_SPEC_REJECTED = "spec_rejected"  # verify-forward positions the target refused
WASTE_CHUNK_OVERRUN = "chunk_overrun"  # decoded past a finish inside a fused chunk
WASTE_DISCONNECTED = "disconnected"  # decoded for a client that already hung up
WASTE_SHED_AFTER_PREFILL = "shed_after_prefill"  # prefilled, then failed/shed
# One decode row of the mixed step dispatched behind the step whose token,
# seen a step later, ended the stream (EOS, a stop id, a client gone; ISSUE 40).
WASTE_OVERSHOOT = "overshoot"
WASTE_REASONS = (WASTE_SPEC_REJECTED, WASTE_CHUNK_OVERRUN, WASTE_DISCONNECTED,
                 WASTE_SHED_AFTER_PREFILL, WASTE_OVERSHOOT)


class SchedulerSaturatedError(RuntimeError):
    """The scheduler's bounded wait queue is full: the caller must shed
    (429 + Retry-After at the serving edge) instead of queueing
    unboundedly — an unbounded deque under sustained overload grows until
    every queued client has long since timed out (ISSUE 2)."""

    def __init__(self, queue_depth: int) -> None:
        super().__init__(f"scheduler queue full ({queue_depth} waiting)")
        self.queue_depth = queue_depth


class SchedulerStoppedError(RuntimeError):
    """Submit against a stopped scheduler (ISSUE 7): during a supervised
    engine restart the old scheduler's loop is gone — enqueueing there
    would hang the client forever. The serving edge maps this to a
    retryable 503 (the replacement scheduler takes over moments later)."""


@dataclass
class GenRequest:
    prompt_ids: list[int]
    max_tokens: int = 64
    temperature: float = 0.0
    top_p: float = 1.0
    stop_token_ids: frozenset[int] = frozenset()
    callback: TokenCallback = lambda *a: None
    # Optional step-boundary hook (streaming fast path): called on the
    # scheduler thread after every engine step in which ``callback``
    # received at least one token for this request (and after a failure
    # callback). A consumer buffering tokens in ``callback`` can hand
    # them to the event loop HERE — one call_soon_threadsafe (a loop
    # wakeup, i.e. a socketpair write syscall) per decode step instead
    # of one per token. None keeps the per-token contract unchanged.
    flush_callback: Callable[[], None] | None = None
    request_id: str = ""
    embeds: object = None  # (T, H) multimodal embedding override row
    seed: int | None = None  # reproducible sampling (OpenAI `seed`)
    # Set by the serving edge when the client abandoned the stream (ISSUE
    # 6 wasted-work attribution): the scheduler keeps decoding to the
    # finish condition, but every further token is billed to
    # engine.wasted_tokens{reason="disconnected"} instead of goodput.
    disconnected: bool = False
    # Per-request phase clock (ISSUE 3 observability): epoch-ns stamps for
    # submit → admit (queue.wait) → first_token (prefill) → finish
    # (decode), written by the scheduler as the request crosses each
    # boundary. The serving sidecar materializes trace child spans and
    # queue-wait/TPOT histograms from these — span timestamps are epoch
    # ns, hence time_ns() rather than the monotonic clock. The sidecar's
    # HTTP edge adds its own two (ISSUE 24): recv (first line of the chat
    # handler, before parse, template and tokenise) and first_write (the
    # first content frame handed to the transport).
    phase_ns: dict[str, int] = field(default_factory=dict)
    # KV-pressure preemption bookkeeping (ISSUE 7): how many times this
    # request has been descheduled (slot + pages released, re-enqueued
    # with prompt+generated-so-far for recompute-style resume), bounded
    # by the scheduler's per-request budget so livelock degrades to a
    # clean failure; resume_generated carries the emitted-token count
    # across preemptions so max_tokens spans the whole stream.
    preempt_count: int = 0
    resume_generated: int = 0
    # Structured outputs (ISSUE 13): a structured.GrammarSession when the
    # request carries response_format json_object/json_schema — the host
    # mirror of the device-side mask automaton (fed one emitted token at
    # a time in _emit, so preemption resume, continuation splices, and
    # speculative proposal repair always know the exact state); plus the
    # request's OpenAI logit_bias map, applied via the same additive-bias
    # device buffer the masks ride.
    grammar: object = None
    logit_bias: dict | None = None


@dataclass
class _SlotState:
    req: GenRequest
    pos: int  # tokens currently written to the cache row
    pending_token: int  # sampled but not yet written
    pending_logprob: float
    generated: int = 1  # pending token counts as generated
    # Speculative decoding bookkeeping (engine.spec): how many tokens the
    # draft model's cache holds, and the ≤2 emitted tokens it has not
    # ingested yet (serving/speculative.py invariants).
    draft_len: int = 0
    catchup: tuple = ()
    # Prompt-lookup drafting (engine.spec_ngram): the request's full
    # token stream (prompt + emitted, incl. the pending token) —
    # proposals are n-gram continuations found in it (ngram_propose).
    history: list | None = None
    # Preemption support (ISSUE 7): every emitted token, recorded only
    # while the scheduler's preemption budget is armed — a preempted
    # request resumes by re-prefilling prompt + out_tokens, so the
    # serving edge neither drops nor repeats a token.
    out_tokens: list = field(default_factory=list)
    # Admission sequence number: larger = younger. Preemption picks the
    # youngest victim (least sunk prefill/decode cost).
    seq: int = 0
    # On-device stopping (ISSUE 14): True once this request finished on
    # a criterion the device stop state also enforces (stop token in
    # the shipped table, max_tokens budget, cache-row exhaustion,
    # grammar completion) — the early-exit carry froze the row at the
    # same step, so trailing chunk tokens were never computed and must
    # not be billed as chunk_overrun waste. Stays False for host-only
    # finishes (stop strings, disconnects), which the device over-ran.
    device_stopped: bool = False


def ngram_propose(history: list, K: int, max_n: int = 3) -> list:
    """Prompt-lookup draft: continue the stream's trailing n-gram.

    Finds the most recent earlier occurrence of the last n tokens
    (longest n ≤ max_n first) and proposes the K tokens that followed
    it. On repetitive text (code, quoting, templated prose) the target
    accepts long prefixes — measurable speedup with ZERO draft weights
    (round-4 verdict next #7). No match → repeat the last token (a
    cheap guess; rejected proposals cost nothing extra since the verify
    forward prices K+1 positions at one weight stream regardless).
    """
    H = len(history)
    for n in range(min(max_n, H - 1), 0, -1):
        tail = history[-n:]
        # Most recent occurrence strictly before the trailing one.
        for i in range(H - n - 1, -1, -1):
            if history[i:i + n] == tail and i + n < H:
                cont = history[i + n:i + n + K]
                if cont:
                    return (cont + [cont[-1]] * K)[:K]
    return [history[-1]] * K


# pending_token sentinel: the slot's first token is still a prefill
# future (async admission); resolved when its handle is processed.
_TOKEN_PENDING = -1


@dataclass
class _Inflight:
    """A submitted-but-unfetched decode chunk: the engine handle, the
    slot→state snapshot at submit time, and its step count (the position
    offset for the next chained submit's page allocation).

    The snapshot holds the _SlotState OBJECTS, not just slot ids: a slot
    can finish mid-flight, be released, and be re-admitted to a NEW
    request while this chunk is still on device. Emitting this chunk's
    tokens into the new occupant's stream was exactly the round-3
    regression (VERDICT r3 weak #1) — _process_chunk emits only when the
    slot's current state IS the snapshotted state (identity check)."""

    handle: object
    states: dict
    n_steps: int
    # What the submit stamped (ISSUE 24; None with no observer attached):
    # submit_ts on the span clock and the dispatch's starved_ms /
    # inflight_at_dispatch / host_gap_ms. They land on this chunk's own
    # decode record, whose [start_ts, ts] is the fetch and emit a chunk or
    # two later; the submit's phases ride the engine handle.
    submit: dict | None = None


@dataclass
class _MixedInflight:
    """A mixed step dispatched and not yet fetched (ISSUE 40). At most one
    exists: the program behind it (the next mixed step, or the fresh decode
    chunk that ends the train) is dispatched first, reading on the device
    the tokens this step sampled, and then this step is fetched and
    processed while that program runs.

    ``states`` holds the _SlotState OBJECTS of the rows that sample a token
    for the host to emit, decode rows and the rows whose prompt ends here
    (same identity contract as ``_Inflight``): until the step is processed
    their ``pos`` / ``generated`` / ``pending_token`` are one token behind
    the device (``Scheduler._ahead``). ``first`` are the rows whose prompt
    ends here, registered in ``_slots`` at dispatch with ``_TOKEN_PENDING``.
    ``retired`` are decode rows whose token is their request's last by its
    budget: their slot went back at dispatch, their state waits in
    ``Scheduler._retiring`` for that token."""

    handle: object
    rows: list
    n_decode: int
    states: dict
    first: list
    t0: float
    context: int  # Σ kv length over the rows, for the record
    retired: dict = field(default_factory=dict)
    # The record's stamps; None / empty with no observer at the dispatch.
    phases: dict | None = None
    spans: dict = field(default_factory=dict)
    # Dispatched behind an unfetched step (its record then runs from its
    # fetch, with the dispatch as ``submit_ts``, like a decode chunk's).
    behind: bool = False


@dataclass
class _PendingPrefill:
    """A submitted-but-unfetched admission batch: the engine prefill
    handle plus the (request, slot) pairs awaiting their first token."""

    handle: object
    items: list
    submit: dict | None = None  # the dispatch's stamps, as on _Inflight


class Scheduler:
    def __init__(self, engine: Engine, logger: Any = None, max_queue_depth: int = 0,
                 preempt_max: int = 0, preempt_high_water: float = 0.0,
                 clock: Any = None) -> None:
        from inference_gateway_tpu.logger import NoopLogger
        from inference_gateway_tpu.resilience.clock import MonotonicClock

        self.engine = engine
        self.logger = logger or NoopLogger()
        # Injectable monotonic clock (PR 1 discipline, enforced by
        # graftlint clock-discipline): liveness stamps read through it
        # so tests can drive staleness without real waiting. Epoch
        # phase stamps (phase_ns) stay on time.time_ns — span
        # timestamps are wall-clock by definition.
        self.clock = clock or MonotonicClock()
        # Bounded admission (0 = unbounded): submit raises
        # SchedulerSaturatedError past this many waiting requests.
        self.max_queue_depth = max_queue_depth
        # KV-pressure preemption (ISSUE 7): 0 disables (page exhaustion
        # fails the request, the pre-preemption behavior). >0 arms it:
        # on recoverable page exhaustion the youngest running request is
        # descheduled (slot + pages released, re-enqueued with
        # prompt+generated-so-far) instead of anyone erroring, at most
        # preempt_max times per request. preempt_high_water (0 = off,
        # else a KV-utilization fraction) additionally preempts the
        # youngest running request at admission time when utilization is
        # above the mark and requests are waiting — FIFO fairness under
        # sustained pressure.
        self.preempt_max = preempt_max
        self.preempt_high_water = preempt_high_water
        self.preemptions = 0  # exported metric
        # Called on the scheduler thread after every preemption with the
        # trigger reason ("kv_pressure" | "high_water") — the sidecar
        # wires it to the engine.preemptions otel counter.
        self.on_preempt: Callable[[str], None] | None = None
        self._waiting: deque[GenRequest] = deque()
        self._slots: dict[int, _SlotState] = {}
        self._free = list(range(engine.config.max_slots))
        self._wake = threading.Condition()
        self._stop = False
        self._ids = itertools.count()
        self._thread: threading.Thread | None = None
        # FIFO of in-flight handles: _PendingPrefill admissions and at
        # most one _Inflight decode chunk (the pipeline).
        self._handles: deque = deque()
        self.queue_depth = 0  # exported metric
        # Speculative-decoding acceptance telemetry (exported via the
        # sidecar /metrics): rounds =
        # draft+verify passes, emitted = tokens they produced (1..K+1
        # each), slot_rounds = per-slot round participations.
        self.spec_rounds = 0
        self.spec_emitted = 0
        self.spec_slot_rounds = 0
        self.spec_accepted = 0   # drafts accepted, of a model that drafts for itself (ISSUE 56)
        # Acceptance-adaptive n-gram speculation (EngineConfig
        # spec_adaptive): rolling window + probe state machine.
        self._spec_on = True
        self._probe_rounds_left = 0
        self._normal_steps = 0
        self._win_emitted = 0
        self._win_slot_rounds = 0
        # Liveness: wall-clock of the last completed engine step. The
        # sidecar /health endpoint flags "degraded" when requests are
        # active but no step has completed recently (wedged device).
        self.last_step_time = self.clock.now()
        # Monotone progress counter for the engine hang watchdog (ISSUE
        # 7): unlike last_step_time (real monotonic clock) a counter can
        # be compared on an injected virtual clock, so the watchdog is
        # zero-sleep testable. step_ewma is a smoothed per-step wall
        # time (updated in _record_step when the timeline is attached)
        # the watchdog derives its device-step deadline from.
        self.steps_completed = 0
        self.step_ewma = 0.0
        # Admission bookkeeping for preemption: monotone sequence so the
        # youngest victim is well-defined, and a free-page-count latch
        # that keeps a pages-starved admission from busy-retrying every
        # loop pass (it re-arms the moment any release/evict changes the
        # pool).
        self._admit_seq = itertools.count()
        self._page_wait: int | None = None
        # The batch currently inside engine.prefill_submit: popped from
        # _waiting but not yet registered in _slots, so a supervised
        # restart's abort_all would otherwise miss it — exactly where a
        # wedged prefill leaves its requests (written only on the
        # scheduler thread; abort_all reads it).
        self._admitting: list[GenRequest] = []
        self._aborted = False
        # Optional decode-step timeline (ISSUE 4, otel/profiling.py
        # StepTimeline): every processed prefill/decode/spec step is
        # recorded with its wall time, kind, batch occupancy, tokens
        # emitted, and KV utilization. None (the default) keeps the hot
        # path at a single attribute check per chunk.
        self.timeline = None
        # Work the engine did that no client saw, in tokens by reason
        # (engine.wasted_tokens{reason}): written by the scheduler thread
        # alone, every reason present from the start so a reader never
        # meets a dict that changes size; the sidecar hands its growth to
        # otel and shows it in /debug/status.
        self.wasted: dict[str, int] = dict.fromkeys(WASTE_REASONS, 0)
        # Optional device observatory (ISSUE 19,
        # otel/device_observatory.DeviceObservatory): when a step's wall
        # time includes an XLA recompile, the timeline record says so —
        # a 2-second decode step with recompiled=1 is a shape-stability
        # incident, not load. Same None-is-free discipline.
        self.observatory = None
        self._recompiles_seen = 0
        # Timeline failure damping (ISSUE 6 satellite): a broken record
        # path must not logger.error once per engine step forever —
        # consecutive failures are rate-limited and the timeline is
        # disabled outright after _TIMELINE_MAX_FAILURES in a row.
        self._timeline_failures = 0
        # Host-gap and starvation instrumentation (ISSUE 14 satellite,
        # generalised by ISSUE 24), perf_counter_ns stamps, only taken
        # while an observer is attached (same None-is-free discipline).
        # _dev_touch: the most recent completed device interaction
        # (submit returned / fetch materialized); the wall time from
        # there to the NEXT dispatch is that dispatch's host_gap_ms.
        # _starve_t0: the moment the programs dispatched and not yet
        # fetched (len(_handles), plus a synchronous step's own) last
        # fell to zero WHILE work was pending — a slot active or a
        # request waiting; an idle scheduler has none, and a request's
        # arrival into one starts the clock at that request's submit
        # stamp. The next dispatch's return closes it as that record's
        # starved_ms: time the device had nothing queued although the
        # scheduler had work.
        # _cursor (ISSUE 38): the clock's ledger by cause. It opens and
        # closes with _starve_t0 (_stamp_fetch / run()'s wake /
        # _stamp_dispatch) and is marked by every phase_span / cause_span
        # this thread enters and leaves while an observer is attached
        # (run() binds it), so a record's starved_by sums to its
        # starved_ms and a span that ran behind a program in flight
        # charges nothing. What lies under no span is charged to "loop".
        self._dev_touch: int | None = None
        self._starve_t0: int | None = None
        self._cursor = CauseCursor()
        # Delivery after dispatch (ISSUE 29). A flush costs the scheduler
        # thread the event loop's turn (~0.3 ms a request: the wake-up
        # write hands the GIL over and the loop serialises that stream's
        # frames before it comes back). Chained chunks hide it behind the
        # chunk already on the device; an admission's mixed steps and its
        # fresh decode submit found the device empty. There the decode
        # rows' flushes wait on this list and _deliver_deferred runs them
        # right after the next dispatch returns. Nothing is held without
        # a dispatch behind it: whenever run() is back at its top the list
        # is empty, or the submit stood back for an admission that is due
        # (_admission_due): the pass opens with it, on an empty device,
        # and delivers behind its first step.
        self._deferred: list[GenRequest] = []
        self.flushes_deferred = 0   # running totals, /debug/status
        self.flushes_immediate = 0
        # One step ahead of the host (ISSUE 40): the mixed step dispatched
        # and not yet fetched. It outlives _admit_mixed's loop and the call
        # (a closed loop's train is mostly one mixed step a pass, pass after
        # pass); whatever needs the host's state whole settles it first
        # (_settle_mixed). _retiring: states whose LAST token that step
        # holds and whose slot already went back (written on the scheduler
        # thread only; abort_all reads it, as it reads _admitting).
        self._mixed_inflight: _MixedInflight | None = None
        self._retiring: list[_SlotState] = []
        # Mixed steps and fresh decode chunks by where their rows' tokens
        # came from (/debug/status): any from an unfetched block, or all
        # from the host.
        self.pipelined_submits = 0
        self.host_stepped_submits = 0
        # The most decode chunks ever dispatched and unfetched at once
        # (/debug/status; ISSUE 48): pipeline_depth + 1, right after a
        # pass's submit and before it fetches the oldest.
        self.chunks_in_flight_max = 0

    def active_requests(self) -> int:
        return len(self._slots)

    # -- public API ----------------------------------------------------
    def submit(self, req: GenRequest) -> str:
        if not req.request_id:
            req.request_id = f"req-{next(self._ids)}"
        req.phase_ns.setdefault("submit", time.time_ns())
        limit = self.engine.context_window() - 1
        if len(req.prompt_ids) > limit:
            req.prompt_ids = req.prompt_ids[-limit:]
        with self._wake:
            if self._stop:
                raise SchedulerStoppedError("scheduler stopped (engine restarting)")
            if self.max_queue_depth and len(self._waiting) >= self.max_queue_depth:
                raise SchedulerSaturatedError(len(self._waiting))
            self._waiting.append(req)
            self.queue_depth = len(self._waiting)
            self._wake.notify()
        return req.request_id

    def start(self) -> None:
        self._thread = threading.Thread(target=self.run, name="scheduler", daemon=True)
        self._thread.start()

    def stop(self) -> None:
        with self._wake:
            self._stop = True
            self._wake.notify()
        if self._thread:
            self._thread.join(timeout=10)

    def cancel(self, req: GenRequest) -> None:
        """Best-effort deschedule of ONE request — the planned-migration
        path (ISSUE 11): the serving edge has already ended the client
        stream (no terminal frame; a continuation-capable gateway splices
        it onto another replica), so this replica must stop spending
        compute on it. A still-queued request is dropped before it ever
        prefills; an admitted one is marked disconnected, which the next
        emission turns into termination + slot/KV release (the existing
        abandoned-stream path). Never raises; safe from the event loop."""
        with self._wake:
            try:
                self._waiting.remove(req)
                self.queue_depth = len(self._waiting)
            except ValueError:
                pass
            self._wake.notify()
        req.disconnected = True

    def abort_all(self) -> int:
        """Fail every queued and in-flight request with finish_reason
        "error" (retryable at the gateway edge) and stop the loop —
        the supervised-restart path (ISSUE 7): the scheduler thread may
        be wedged inside a device call forever, so cleanup cannot be
        delegated to it. ``_slots`` is only READ here (the wedged thread
        owns mutation; the replacement scheduler gets a fresh table),
        and if the old thread ever unwedges it exits on ``_stop`` —
        late emissions land on callbacks that already saw a terminal
        event, which every consumer tolerates. Returns the number of
        requests failed. Idempotent: a second call (the watchdog tripping
        again after a failed engine rebuild) fails only newly queued
        requests, never re-firing terminal callbacks for the same
        slots."""
        with self._wake:
            self._stop = True
            waiting = list(self._waiting)
            self._waiting.clear()
            self.queue_depth = 0
            self._wake.notify_all()
        failed = 0
        for req in waiting:
            self._fail_request(req)
            failed += 1
        if not self._aborted:
            self._aborted = True
            # A batch wedged INSIDE prefill_submit is in neither _waiting
            # nor _slots — _admitting is the only record of it.
            for req in list(self._admitting):
                self._fail_request(req)
                failed += 1
            for st in list(self._slots.values()) + list(self._retiring):
                self._fail_request(st.req)
                failed += 1
        # The wedged thread may never reach its next dispatch.
        self._deliver_deferred()
        return failed

    # -- adaptive speculation (EngineConfig.spec_adaptive) -------------
    def _spec_mode_active(self) -> bool:
        """True when the CURRENT pass serves via speculative rounds."""
        cfg = self.engine.config
        if not self.engine.spec_ngram or not cfg.spec_adaptive:
            return True
        return self._spec_on

    def _spec_turn(self) -> bool:
        """Whether this loop pass runs a speculative round. Always True
        for model-draft spec and non-adaptive n-gram; adaptive n-gram
        disables itself on low acceptance (the normal pipelined loop
        takes over) and re-probes every spec_probe_every normal steps."""
        cfg = self.engine.config
        if self._spec_mode_active():
            return True
        # _normal_steps advances by chunk length in _process_chunk (real
        # engine steps, not loop passes).
        if not self._slots or self._normal_steps < cfg.spec_probe_every:
            return False
        # Probe due: make host state authoritative (drain the chunk
        # pipeline) and invalidate the device carry — the spec rounds
        # advance positions the carried chain doesn't know about.
        self._drain_all()
        self.engine._dev_carry = None
        self._spec_on = True
        self._probe_rounds_left = cfg.spec_probe_rounds
        self._win_emitted = self._win_slot_rounds = 0
        return True

    def _spec_adapt(self, emitted: int, slot_rounds: int) -> None:
        cfg = self.engine.config
        if not self.engine.spec_ngram or not cfg.spec_adaptive:
            return
        self._win_emitted += emitted
        self._win_slot_rounds += slot_rounds
        if self._probe_rounds_left > 0:
            self._probe_rounds_left -= 1
            if self._probe_rounds_left > 0:
                return  # let the probe window fill before judging
        if self._win_slot_rounds < cfg.spec_probe_rounds:
            return
        rate = self._win_emitted / self._win_slot_rounds
        if rate < cfg.spec_min_tokens_per_round:
            self._spec_on = False
            self._normal_steps = 0
            self.logger.info("adaptive speculation off",
                             "tokens_per_slot_round", round(rate, 3))
        # Sliding epochs: judge each window on fresh data.
        self._win_emitted = self._win_slot_rounds = 0

    # -- core loop -----------------------------------------------------
    def run(self) -> None:
        """Pipelined serving loop: at most one decode chunk in flight,
        and admissions that never stall it.

        Steady state submits chunk N+1 (chained off device-resident
        carry — no host round-trip) BEFORE fetching chunk N's tokens, so
        the host↔device round trip overlaps chunk N+1's execution
        instead of serializing with it. Admission is asynchronous
        too: prefill results are scattered into the chained device state
        on-device (engine._admit_scatter_fn_ee), so a prefill dispatch slots
        between chunks with no drain. Handles (prefills + chunks) are
        processed FIFO — a chunk that includes freshly admitted slots is
        always processed after their prefill, so host bookkeeping sees
        first tokens in order. Only failure recovery (device carry
        invalidated) drains the queue and resubmits from host state.

        On a mixed-step engine an admission is a TRAIN of mixed steps
        (_admit_mixed) that runs one step ahead of the host (ISSUE 40):
        each step, and the fresh decode chunk that ends the train, is
        dispatched before the step in front of it is fetched and reads
        that step's tokens on the device; the host processes step N while
        step N+1 runs. What the train still waits for is the drain before
        its first step (the chunks in flight) and that first step's own
        build.
        """
        while True:
            with self._wake:
                while (not self._stop and not self._waiting and not self._slots
                       and not self._handles and self._mixed_inflight is None):
                    self._wait_for_work()
                if self._stop:
                    break
            bind_cursor(self._cursor if self._observing else None)
            with cause_span("sched.loop"):
                self._pass()
        bind_cursor(None)
        if not self._aborted:
            self._settle_mixed()
        self._deliver_deferred()  # stopped

    def _wait_for_work(self) -> None:
        """One tick of run()'s wait with nothing to do (holding _wake).
        Waiting is neither starvation nor a host gap: the starvation clock
        starts with the arrival that ends the wait, at that request's
        ``submit`` stamp (not before this tick began: a requeued request
        keeps an old one), so the way from there to this thread awake is
        charged to ``wake``, the span this tick runs under."""
        cursor = self._cursor if self._observing else None
        bind_cursor(cursor)
        with cause_span("sched.wake"):
            t_tick = time.perf_counter_ns()
            self._wake.wait(timeout=0.2)
            if cursor is not None and self._waiting:
                submit_ns = self._waiting[0].phase_ns.get("submit")
                now = time.perf_counter_ns()
                t0 = now if submit_ns is None else min(max(perf_ns_of(submit_ns), t_tick), now)
                self._starve_t0 = t0
                self._dev_touch = None
                cursor.start(t0)

    def _pass(self) -> None:
        """One pass of run()'s loop with work to do."""
        if self.preempt_max and self.preempt_high_water > 0:
            self._maybe_high_water_preempt()
        want_admit = self._admission_due()
        if self.engine.spec and self._spec_turn():
            # Speculative rounds are synchronous (draft + verify per
            # round, 1..K+1 tokens out); no chunk pipeline.
            if want_admit:
                try:
                    self._admit()
                except Exception as e:
                    self.logger.error("scheduler admission error", e)
            if self._slots:
                before = (self.spec_emitted, self.spec_slot_rounds)
                try:
                    if self.engine.spec_ngram:
                        self._spec_step_ngram()
                    else:
                        self._spec_step()
                except Exception as e:
                    self._fail_after_decode_error(e)
                    return
                self._spec_adapt(self.spec_emitted - before[0],
                                 self.spec_slot_rounds - before[1])
            return
        if want_admit:
            # A single bad request (prompt over the largest bucket in
            # a mode with no chunked fallback, KV page pool
            # exhausted, ...) must never kill the scheduler thread —
            # that would wedge every queued and active request
            # (advisor round-1 medium).
            try:
                # Whatever of an admission lies under no narrower span
                # (popping the batch, the rows' bookkeeping between
                # steps) is the ledger's "rows".
                with cause_span("sched.admit.rows"):
                    if getattr(self.engine, "mixed_ok", False):
                        self._admit_mixed()
                    else:
                        self._admit()
            except Exception as e:
                # _admit's internal paths fail the affected requests
                # themselves; reaching here means bookkeeping OUTSIDE
                # those guards broke. Never silent (round-2 verdict
                # weak #4): a recurring admission bug must be visible.
                self.logger.error("scheduler admission error", e)
        if self._slots:
            chain = self.engine._dev_carry is not None
            if not chain and self._mixed_inflight is None:
                # First chunk ever, or recovery after a device
                # failure: host state must be authoritative, so
                # process every outstanding handle first. (Behind an
                # admission's train nothing is outstanding but its last
                # mixed step, whose tokens the chunk reads on the device.)
                self._drain_all()
            h = self._submit_chunk(chain=chain)
            if h is not None:
                self._handles.append(h)
                self.chunks_in_flight_max = max(
                    self.chunks_in_flight_max,
                    sum(isinstance(x, _Inflight) for x in self._handles))
            if h is not None or not self._admission_due():
                # The chunk is behind it on the device (or no program
                # follows): fetch and process the train's last step. With
                # an admission due it stays, and the next pass's first
                # mixed step is dispatched behind it.
                self._settle_mixed()
            if h is not None and not chain:
                # Behind the fresh chunk: what waited for a dispatch, and
                # the rows of the step just processed.
                self._deliver_deferred(h.submit, getattr(h.handle, "phases", None))
        else:
            # No active request: any leftover tail chunks carry only
            # already-finished streams — drain them now, or the loop
            # busy-spins on an unprocessable pure-chunk tail.
            self._drain_all()
        # Deliveries still waiting: no dispatch came. Where the submit
        # stood back for an admission, the next pass opens with it and
        # delivers behind its first step; after a submit that failed,
        # or the last request finishing, deliver now.
        if self._deferred and not self._admission_due():
            self._deliver_deferred()
        self._process_handles()

    def _admission_due(self) -> bool:
        """A request waits, a slot is free, and pages are not what it
        waits for: run() admits at its next pass, and _submit_chunk
        stands back for it."""
        with self._wake:
            return bool(self._waiting and self._free) and self._admit_ready()

    def _process_handles(self) -> None:
        """Process outstanding handles FIFO, leaving the newest
        `pipeline_depth` decode chunks unfetched. The next pass submits
        one more and only then blocks in the oldest's fetch, so the
        device has the next program queued whenever a chunk ends, and
        while the thread blocks it holds the running chunk and that many
        behind it: none of them can be cancelled, and an arrival is seen
        only when the fetch returns (ISSUE 48).

        The queue may only be left holding a pure chunk tail — a pending
        prefill is always resolved before any chunk submitted after it,
        so host bookkeeping sees a request's first token before its
        decode continuation (FIFO emission order)."""
        depth = max(self.engine.config.pipeline_depth, 1)
        while self._handles:
            if (len(self._handles) <= depth
                    and all(isinstance(h, _Inflight) for h in self._handles)):
                break
            self._process_one(self._handles.popleft())

    def _drain_all(self) -> None:
        self._settle_mixed()  # older than any chunk: one is dispatched behind it at most
        while self._handles:
            self._process_one(self._handles.popleft())

    def _process_one(self, h: object, defer: bool = False) -> None:
        try:
            if isinstance(h, _Inflight):
                self._process_chunk(h, defer)
            else:
                self._process_prefill(h)
        except Exception as e:
            # Both processors guard their fetch and release paths;
            # reaching here means emission bookkeeping broke. Never let
            # it kill the scheduler thread.
            self._fail_after_decode_error(e)

    def _flush_emits(self, req: GenRequest, defer: bool = False) -> None:
        """Step-boundary flush for token-batching consumers; a dead
        client's flush must never kill the batch (same contract as
        ``callback``). ``defer``: the device has nothing queued and the
        caller dispatches next, so the flush waits for that dispatch
        (_deliver_deferred). A later immediate flush of the request
        carries the waiting tokens with it, in order."""
        if req.flush_callback is None:
            return
        if defer:
            self._deferred.append(req)
            return
        self.flushes_immediate += 1
        try:
            req.flush_callback()
        except Exception:
            pass

    def _deliver_deferred(self, spans: dict | None = None,
                          phases: dict | None = None) -> None:
        """Run the flushes that waited for a dispatch, in order. Called
        right after one returned, with that step's ``spans`` and
        ``phases`` to stamp (``deferred_flushes``, ``deliver``), and bare
        wherever a pass ends without one."""
        todo, self._deferred = self._deferred, []
        if spans is not None:
            spans["deferred_flushes"] = spans.get("deferred_flushes", 0) + len(todo)
        with phase_span(phases, "deliver", "sched.deliver"):
            for req in todo:
                try:
                    req.flush_callback()
                except Exception:
                    pass
        self.flushes_deferred += len(todo)

    def _fail_request(self, req: GenRequest) -> None:
        req.phase_ns.setdefault("finish", time.time_ns())
        try:
            req.callback(0, 0.0, True, "error")
        except Exception:
            pass
        self._flush_emits(req)

    def _fail_slot(self, slot: int, reason: str = "error") -> None:
        """Fail + release ONE slot, guarding each step: cleanup of one
        victim must never abort cleanup of the rest or kill the
        scheduler thread (advisor round-2: _release raising mid
        failure-path was exactly the crash this code defends against)."""
        st = self._slots.pop(slot, None)
        if st is not None:
            self._fail_request(st.req)
            # The prompt was prefilled and some tokens may have been
            # decoded, but the stream ends in "error": all of it was
            # work no client benefits from (ISSUE 6). For a resumed
            # request (ISSUE 7), prompt_ids already contains the
            # pre-preemption tokens that generated also counts —
            # subtract resume_generated so they are not billed twice.
            self._wasted(WASTE_SHED_AFTER_PREFILL,
                         len(st.req.prompt_ids) + st.generated
                         - st.req.resume_generated)
        try:
            self._release(slot, reason,
                          frozen=st.device_stopped if st is not None else False)
        except Exception as e:
            self.logger.error("slot release failed", e, "slot", slot)

    def _fail_after_decode_error(self, e: Exception) -> None:
        """Fail the slot tagged on the exception (the engine tags every
        host-side per-slot failure with .slot — OutOfPagesError and page
        bookkeeping), or — if unattributable (a batched device error) —
        every active slot, so clients see finish_reason "error" instead
        of a hung stream."""
        slot = getattr(e, "slot", None)
        if slot is not None and slot in self._slots:
            if (self.preempt_max and isinstance(e, OutOfPagesError)
                    and getattr(e, "recoverable", True)
                    and self._preempt_for_pressure(slot)):
                # Pressure relieved by descheduling the youngest budgeted
                # request — nobody fails; the next loop pass resubmits.
                return
            victims = [slot]
            self.logger.warn("decode error attributed to slot", "slot", slot, "err", repr(e))
        else:
            victims = list(self._slots)
            self.logger.error("unattributable decode error; failing batch", e,
                              "victims", len(victims))
        for s in victims:
            self._fail_slot(s)

    # -- KV-pressure preemption (ISSUE 7) ------------------------------
    def _admit_ready(self) -> bool:
        """False while a pages-starved admission waits for the pool to
        change. Re-arms the moment the free-page count moves (any
        release or eviction), or when no active slot is left to free
        pages (so a failed release can never park admission forever)."""
        if self._page_wait is None:
            return True
        alloc = self.engine.allocator
        if alloc is None or not self._slots or alloc.free_page_count() != self._page_wait:
            self._page_wait = None
            return True
        return False

    def _resumable(self, st: _SlotState) -> bool:
        """Whether the slot's request can re-enter admission after a
        preemption: prompt + generated-so-far must still fit the
        engine's admittable-prompt limit (paged mode has no chunked
        fallback for the re-prefill)."""
        req = st.req
        resume_len = len(req.prompt_ids)
        if st.pending_token != _TOKEN_PENDING:
            resume_len += len(st.out_tokens)
        return 0 < resume_len <= self.engine.max_prompt_len(
            multimodal=req.embeds is not None)

    def _pick_victim(self) -> int | None:
        """Youngest active slot whose request still has preemption
        budget and whose resume prompt is admittable; None when nobody
        qualifies (degrade to today's clean failure)."""
        best = None
        for slot, st in self._slots.items():
            if st.req.preempt_count >= self.preempt_max:
                continue
            if not self._resumable(st):
                continue
            if best is None or st.seq > self._slots[best].seq:
                best = slot
        return best

    def _preempt(self, slot: int, reason: str) -> None:
        """Deschedule one running request: release its slot and KV pages
        and re-enqueue it with prompt + generated-so-far as the new
        prompt (recompute-style resume; PrefixCache makes the re-prefill
        cheap when enabled). Emitted tokens are never re-emitted — the
        resumed prefill's first sampled token is the next NEW token, so
        the serving edge sees one uninterrupted stream. In-flight chunks
        still carrying this slot are excluded by the state-identity
        check in _process_chunk/_process_prefill."""
        st = self._slots.pop(slot)
        req = st.req
        req.preempt_count += 1
        if st.pending_token != _TOKEN_PENDING and st.out_tokens:
            req.prompt_ids = list(req.prompt_ids) + st.out_tokens
            req.resume_generated += len(st.out_tokens)
        self.preemptions += 1
        self._release_guarded(slot, "preempted")
        with self._wake:
            if reason == "high_water":
                # High-water preemption makes room for the waiting head:
                # the victim goes to the back, behind it.
                self._waiting.append(req)
            else:
                # Pressure preemption resumes as soon as pages free up —
                # the client already holds a live, half-served stream.
                self._waiting.appendleft(req)
            self.queue_depth = len(self._waiting)
            self._wake.notify()
        self.logger.warn("preempted request under KV pressure",
                         "request", req.request_id, "reason", reason,
                         "resume_prompt", len(req.prompt_ids),
                         "preempt_count", req.preempt_count)
        if self.on_preempt is not None:
            try:
                self.on_preempt(reason)
            except Exception:
                pass

    def _maybe_high_water_preempt(self) -> None:
        """Admission high-water mark (ISSUE 7): sustained KV pressure
        must not starve the waiting head forever — when utilization is
        above the mark with requests waiting, the youngest running
        request yields its slot and pages (and rejoins the queue BEHIND
        the head). Runs every loop pass, independent of free slots: the
        preemption is what frees one."""
        if (not self._waiting or not self._slots
                or self.engine.kv_utilization() < self.preempt_high_water):
            return
        self._settle_mixed()  # a victim resumes from what the host has seen
        victim = self._pick_victim()
        if victim is not None:
            self._preempt(victim, "high_water")

    def _preempt_for_pressure(self, starved: int) -> bool:
        """Decode-time page exhaustion attributed to ``starved``: preempt
        the youngest budgeted request instead of failing anyone. The
        starved slot (often the youngest itself) either gets descheduled
        for a clean resume or keeps running against the freed pages."""
        victim = self._pick_victim()
        if victim is None:
            return False
        self._preempt(victim, "kv_pressure")
        return True

    def _requeue_admission(self, batch: list, slots: list) -> None:
        """Page-starved admission: return the batch's slots and partial
        page allocations and put the requests back at the head of the
        queue (order preserved) instead of failing them. Admission then
        parks until the page pool changes (_admit_ready)."""
        for _req, slot in zip(batch, slots):
            self._release_guarded(slot, "requeue")
        with self._wake:
            for req in reversed(batch):
                self._waiting.appendleft(req)
            self.queue_depth = len(self._waiting)
        alloc = self.engine.allocator
        self._page_wait = alloc.free_page_count() if alloc is not None else None

    # ------------------------------------------------------------------
    def _admit(self) -> None:
        """Move waiting requests into free slots and prefill them.

        Non-speculative mode dispatches the prefill WITHOUT waiting: the
        engine scatters first tokens/positions into the chained device
        state (no pipeline barrier), and the host-side results arrive
        later via the handle queue (_process_prefill emits the first
        tokens). Speculative mode admits synchronously — spec rounds
        need the first token host-side for the draft catch-up block.
        """
        self._settle_mixed()  # the bucketed path is host-stepped
        batch: list[GenRequest] = []
        slots: list[int] = []
        with self._wake:
            while self._waiting and self._free and len(batch) < self.engine.config.max_prefill_batch:
                req = self._waiting.popleft()
                batch.append(req)
                slots.append(self._free.pop())
            self.queue_depth = len(self._waiting)
        if not batch:
            return
        admit_ns = time.time_ns()
        for req in batch:
            # Queue wait ends here: the request owns a slot and its
            # prefill dispatch is imminent.
            req.phase_ns.setdefault("admit", admit_ns)
        embeds = [r.embeds for r in batch]
        seeds = [r.seed for r in batch]
        grammars = [r.grammar for r in batch]
        biases = [r.logit_bias for r in batch]
        self._admitting = batch  # visible to abort_all if prefill wedges
        # Arm the admitted slots' on-device stop state (ISSUE 14): the
        # async-scattered first tokens chain straight into fused chunks,
        # so stop tables and max_tokens budgets must be device-resident
        # before any of those chunks run. The first emitted token counts
        # toward generated (pending counts as 1), hence the -1; resumed
        # requests already spent resume_generated of their budget.
        eos = getattr(self.engine, "_eos_id", None)
        stop_rows = np.stack(
            [build_stop_row(eos, r.stop_token_ids) for r in batch])
        budgets = np.asarray(
            [max(r.max_tokens - r.resume_generated - 1, 0) for r in batch],
            np.int64)
        observing = self._observing
        t_submit = time.perf_counter_ns() if observing else 0
        try:
            handle = self.engine.prefill_submit(
                [r.prompt_ids for r in batch], slots,
                [r.temperature for r in batch], [r.top_p for r in batch],
                embeds=embeds if any(e is not None for e in embeds) else None,
                seeds=seeds if any(s is not None for s in seeds) else None,
                grammars=grammars if any(g is not None for g in grammars) else None,
                biases=biases if any(b for b in biases) else None,
                stop_rows=stop_rows, budgets=budgets,
            )
        except Exception as e:
            self._admitting = []
            if (self.preempt_max and isinstance(e, OutOfPagesError)
                    and getattr(e, "recoverable", True) and self._slots):
                # Admission-time page exhaustion with running requests
                # that will free pages: requeue instead of failing
                # (ISSUE 7) — the batch resumes once the pool changes.
                self._requeue_admission(batch, slots)
                return
            # Fail the whole admission batch (finish_reason "error"),
            # return its slots/pages, keep the scheduler alive.
            for req, slot in zip(batch, slots):
                self._fail_request(req)
                self._release(slot, "error")
            return
        for req, slot in zip(batch, slots):
            self._slots[slot] = _SlotState(
                req, pos=len(req.prompt_ids), pending_token=_TOKEN_PENDING,
                pending_logprob=0.0, draft_len=len(req.prompt_ids),
                generated=req.resume_generated + 1, seq=next(self._admit_seq))
        # Cleared only AFTER the slots are registered: a concurrent
        # abort_all in the gap must find the batch in _admitting OR
        # _slots (a double terminal callback is tolerated; a missed one
        # hangs the client — code-review round 2).
        self._admitting = []
        pending = _PendingPrefill(handle, list(zip(batch, slots)),
                                  self._stamp_dispatch("prefill", t_submit) if observing else None)
        if self.engine.spec and self._spec_mode_active():
            # Spec rounds need first tokens host-side immediately.
            self._process_prefill(pending)
        else:
            # Non-spec — or adaptive speculation parked in the normal
            # loop, which keeps its async-admission overlap.
            self._handles.append(pending)

    def _process_prefill(self, p: "_PendingPrefill") -> None:
        """Materialize a prefill's first tokens and stream them out."""
        observing = self._observing
        t0 = time.perf_counter() if observing else 0.0
        phases: dict | None = {} if observing else None
        try:
            results = self.engine.prefill_fetch(p.handle, phases)  # fetch_wait, fetch_copy
        except Exception as e:
            self.engine._dev_carry = None  # scatter output is poisoned
            self.logger.error("prefill fetch failed; failing admission batch", e)
            for req, slot in p.items:
                if slot in self._slots:
                    del self._slots[slot]
                    self._fail_request(req)
                    self._release_guarded(slot, "error")
            return
        self.last_step_time = self.clock.now()
        self.steps_completed += 1
        if observing:
            # Device interaction completed: host-gap clocks restart here
            # so a prefill fetch between chunks isn't billed as host gap.
            self._stamp_fetch()
        with phase_span(phases, "emit", "sched.emit"):
            for (req, slot), res in zip(p.items, results):
                st = self._slots.get(slot)
                if st is None or st.req is not req:
                    # Failed/released/preempted while in flight — and the
                    # slot may already belong to a NEW request (identity
                    # check, same contract as _Inflight snapshots): these
                    # first tokens describe a stream that no longer runs.
                    continue
                st.pending_token = res.first_token
                st.pending_logprob = res.logprob
                st.catchup = (res.first_token,)
                if self.engine.spec_ngram:
                    st.history = list(req.prompt_ids) + [res.first_token]
                finished, reason = self._emit(st, res.first_token, res.logprob)
                if finished:
                    self._finish(slot, req, reason, frozen=st.device_stopped)
                else:
                    self._flush_emits(req)
        if observing:
            self._record_step("prefill", t0, n_steps=1, batch=len(p.items),
                              tokens=len(results),
                              spans={**(p.submit or {}), "phases": phases})

    # -- ragged mixed-step admission (ISSUE 12) ------------------------
    def _ahead(self, slot: int, st: _SlotState) -> int | None:
        """Where the slot's pending token is. None: on the host
        (``st.pending_token``). Else it is what the unfetched mixed step
        samples for the slot, still on the device, and the value is how
        far ``st.pos`` and ``st.generated`` lie behind it until that step
        is processed: 1 for a decode row of that step, 0 for a row whose
        prompt ends there (registered with the first token counted and
        ``_TOKEN_PENDING`` in its place)."""
        inf = self._mixed_inflight
        if inf is None or inf.states.get(slot) is not st:
            return None
        return 0 if st.pending_token == _TOKEN_PENDING else 1

    def _last_by_budget(self, req: GenRequest, pos: int, generated: int) -> bool:
        """The finish the host can foresee (ISSUE 40): with the cache row at
        ``pos`` and ``generated`` tokens out, counting a token still on the
        device, that token is the request's last by ``max_tokens`` or by
        the context's end, whatever it is. The same two tests as _emit's."""
        return generated >= req.max_tokens or pos + 1 >= self.engine.config.max_seq_len

    def _count_submit(self, from_device: bool) -> str:
        """One mixed step or fresh decode chunk dispatched: counted by where
        its rows' tokens came from, which is its record's ``tokens_from``."""
        if from_device:
            self.pipelined_submits += 1
            return "device"
        self.host_stepped_submits += 1
        return "host"

    def _runs_ahead(self, inf: _MixedInflight) -> bool:
        """Whether the next program may be dispatched before this mixed
        step is fetched. Not while one of the rows it samples for holds a
        grammar: the automaton's state after this step's token is computed
        on the host at emit, and the next program's mask row needs it (the
        state on the device is a follow-up, PERF.md section 7). A branch on
        what the step holds, nothing a user sets."""
        return all(st.req.grammar is None for st in inf.states.values())

    def _build_mixed_rows(self, pending: list) -> tuple[list, int, int]:
        """Descriptor assembly for ONE mixed engine step: a decode row
        per active slot (its pending token advances one position), then
        prefill-chunk rows for the admitting requests, filling whatever
        packed budget remains. Pure host bookkeeping — no device reads
        (graftlint jax-hot-path pins this: a sync here would serialize
        the step against the previous one's results). A slot whose
        pending token the unfetched mixed step holds (_ahead) gets a row
        that reads it on the device (``from_block``), at the position and
        count the slot will have once that step is processed; if that
        token is the request's last by its budget the slot has no row.

        Returns (rows, n_decode, context_tokens): the last is Σ kv
        length over all rows, the record's ``context_tokens``.
        """
        from inference_gateway_tpu.serving.engine import MixedRow

        budget = self.engine.mixed_budget
        # A model that drafts for itself (ISSUE 56): a decoding row has TWO queries,
        # its pending token and the draft, both on the device with their positions;
        # an unfetched round moved the row by one or two, so ``pos`` below is the
        # least it can have come to (what a budget is foreseen by) and the row's
        # ``start`` the farthest (what its pages are reserved for).
        per = getattr(self.engine, "round_tokens", 1)
        rows: list = []
        used = 0
        context = 0
        for slot, st in self._slots.items():
            lag = self._ahead(slot, st)
            if lag is None and st.pending_token == _TOKEN_PENDING:
                continue  # a bucketed admission's prefill future (its handle was drained; defensive)
            req = st.req
            pos = st.pos + (lag or 0)
            if lag is not None and self._last_by_budget(req, pos, st.generated + lag):
                continue
            if per == 2:
                start = min(st.pos + 2 * (lag or 0), self.engine.config.max_seq_len - 2)
                rows.append(MixedRow(
                    slot=slot, token_ids=[0, 0], start=start, kind="decode", temp=req.temperature,
                    top_p=req.top_p, seed=req.seed,
                    mask_state=req.grammar.global_state if req.grammar is not None else 0,
                    from_block=lag is not None))
                used += 2
                context += pos + 2
                continue
            rows.append(MixedRow(
                slot=slot, token_ids=[0 if lag is not None else st.pending_token], start=pos,
                kind="decode", temp=req.temperature, top_p=req.top_p, seed=req.seed,
                mask_state=req.grammar.global_state if req.grammar is not None else 0,
                from_block=lag is not None))
            used += 1
            context += pos + 1
        n_decode = len(rows)
        for item in pending:
            req, slot = item["req"], item["slot"]
            done = item["done"]
            remaining = len(req.prompt_ids) - done
            take = self.engine.prompt_chunk(done, min(remaining, budget - used))
            if take <= 0:
                continue
            rows.append(MixedRow(
                slot=slot, token_ids=req.prompt_ids[done:done + take], start=done,
                kind="prefill", temp=req.temperature, top_p=req.top_p, seed=req.seed,
                mask_state=req.grammar.global_state if req.grammar is not None else 0,
                # (a drafting model's module reads each token beside its successor)
                next_token=req.prompt_ids[done + take] if per == 2 and remaining > take else None))
            used += take
            context += done + take
            item["done"] = done + take
        return rows, n_decode, context

    def _fail_mixed_admission(self, pending: list, e: Exception) -> None:
        """Unrecoverable mixed-step failure: fail the admitting requests
        cleanly, then attribute the step failure to the active batch as
        usual — but an error tagged to an ADMITTING slot was just failed
        here and must not nuke the active batch too. A request whose
        prompt's last chunk was dispatched is an active slot already."""
        pending = [it for it in pending if not it.get("registered")]
        admitting_slots = {it["slot"] for it in pending}
        for item in pending:
            self._fail_request(item["req"])
            self._release_guarded(item["slot"], "error")
        tag = getattr(e, "slot", None)
        if tag is None or tag not in admitting_slots:
            self._fail_after_decode_error(e)
        else:
            self.logger.warn("mixed admission failed", "slot", tag, "err", repr(e))

    def _admit_mixed(self) -> None:
        """Mixed-step admission (ISSUE 12 tentpole): the admitted
        prompts prefill in ragged CHUNKS that share each engine step
        with a decode row per active slot — a long prompt no longer
        serializes ahead of interactive streams (no prefill head-of-line
        blocking), and every step is ONE launch of the one compiled
        mixed program (no bucket padding).

        The chunk loop runs on the scheduler thread, bounded by
        ceil(Σ prompt / free budget) steps, ONE STEP AHEAD of the host
        (ISSUE 40): build step N+1 → dispatch it → deliver what was
        deferred → fetch step N → emit N (finishes, first tokens, slot
        release, flushes) → build N+2. The step dispatched last stays
        unfetched when the loop ends, and across calls: the next call's
        first step, or run()'s fresh decode chunk, goes behind it
        (_mixed_step, _submit_chunk). Decode tokens stream out at every
        step, one step late; when the last chunk of a prompt is
        dispatched the request becomes a regular active slot whose first
        token is on its way. The device carry stays invalidated by every
        mixed step, so the chunk behind the train is a fresh submit, and
        the chunks in flight are drained before the train's first step.
        Requests the ragged program can't serve (multimodal embedding
        overrides) fall back to the bucketed admission path wholesale.
        """
        batch: list[GenRequest] = []
        slots: list[int] = []
        multimodal_head = False
        with self._wake:
            # The embeds check happens under the SAME lock as the pop —
            # a multimodal request enqueued between a peek and the pop
            # must never slip into the ragged path (forward_ragged
            # carries no embedding overrides; serving it from token ids
            # would be plausible wrong output).
            while self._waiting and self._free and len(batch) < self.engine.config.max_prefill_batch:
                if self._waiting[0].embeds is not None:
                    multimodal_head = True
                    break
                req = self._waiting.popleft()
                batch.append(req)
                slots.append(self._free.pop())
            self.queue_depth = len(self._waiting)
        if not batch:
            self._settle_mixed()
            self._deliver_deferred()  # what the pass before carried in: no step follows
            if multimodal_head:
                return self._admit()  # bucketed path carries the embeds
            return
        admit_ns = time.time_ns()
        for req in batch:
            req.phase_ns.setdefault("admit", admit_ns)
        limit = self.engine.max_prompt_len()
        # Registered BEFORE any blocking engine work (the drain below can
        # wedge on a dead device): abort_all must find the popped batch
        # in _admitting or _slots — a missed one hangs the client (same
        # contract as bucketed _admit).
        self._admitting = batch
        # What the admission costs before its first mixed step (ISSUE 24):
        # rides that step's record as ``prep`` {ms, register_ms, drain_ms,
        # drained_steps}.
        prep: dict | None = (dict.fromkeys(("ms", "register_ms", "drain_ms"), 0.0)
                             if self._observing else None)
        with phase_span(prep, "ms", "sched.admit.prep"):
            # Structured admission (ISSUE 13): spans + bias rows must be
            # device-resident (and session bases set) before the first mixed
            # step reads any global mask state. A failed registration
            # (StructuredCapacityError: table budget full of live spans)
            # fails ONLY that request — the bare-raise alternative would
            # leak every popped slot and hang the whole batch (run()'s
            # admission handler only logs; review finding).
            kept: list[GenRequest] = []
            kept_slots: list[int] = []
            for req, slot in zip(batch, slots):
                if req.grammar is not None or req.logit_bias:
                    try:
                        with phase_span(prep, "register_ms", "structured.register_slot"):
                            self.engine.structured_register(slot, req.grammar, req.logit_bias)
                    except Exception as e:
                        self.logger.warn("structured admission failed",
                                         "request", req.request_id, "err", repr(e))
                        self._fail_request(req)
                        self._release_guarded(slot, "error")
                        continue
                kept.append(req)
                kept_slots.append(slot)
            batch, slots = kept, kept_slots
            self._admitting = batch
            if not batch:
                self._admitting = []
                self._settle_mixed()
                self._deliver_deferred()
                return
            # Host state must be authoritative before positions move under
            # the pipeline's feet — and the carry is about to be invalidated.
            # A chunk with another behind it delivers as it always did;
            # the last one leaves the device empty, so its delivery waits
            # for the mixed step's dispatch. (Behind a train's unfetched
            # step there is no chunk: one is dispatched only as that step
            # is settled.) ``drained_steps``: the decode steps dispatched
            # and not yet fetched that this admission waits out (ISSUE 48).
            if prep is not None:
                prep["drained_steps"] = sum(
                    h.n_steps for h in self._handles if isinstance(h, _Inflight))
            with phase_span(prep, "drain_ms", "sched.admit.drain"):
                while self._handles:
                    h = self._handles.popleft()
                    self._process_one(h, defer=not self._handles)
            pending = [{"req": r, "slot": s, "done": 0} for r, s in zip(batch, slots)]
            if self.engine.prefix_cache is not None:
                # Prefix-cache fast path, same as bucketed admission: adopt
                # the longest cached page-aligned prefix and chunk-prefill
                # only the tail (match always leaves ≥1 token to compute).
                with self.engine._lock:
                    for item in pending:
                        shared, matched = self.engine.prefix_cache.match(
                            item["req"].prompt_ids)
                        if shared:
                            self.engine.allocator.adopt_pages(item["slot"], shared)
                            item["done"] = matched
        stepped = dispatch_follows = False
        try:
            while pending:
                kept = []
                for item in pending:
                    req = item["req"]
                    if req.disconnected:
                        self._release_guarded(item["slot"], "disconnected")
                        self._fail_request(req)
                    elif len(req.prompt_ids) > limit:
                        self._release_guarded(item["slot"], "error")
                        self._fail_request(req)
                    else:
                        kept.append(item)
                pending = kept
                if not pending:
                    break
                if not self._mixed_step(pending, prep):
                    return  # requeued or failed, by the step's own handlers
                prep = None  # the admission's first record carried it
                stepped = True
                pending = [it for it in pending if not it.get("registered")]
            dispatch_follows = stepped and bool(self._slots)
        finally:
            self._admitting = []
            if not dispatch_follows:
                # Requeued, failed, no step at all, or nobody left decoding.
                self._settle_mixed()
                self._deliver_deferred()

    def _mixed_step(self, pending: list, prep: dict | None) -> bool:
        """One step of an admission's train: build it, dispatch it BEHIND
        the unfetched step if there is one (its decode rows take their
        token from that step's block on the device), deliver what was
        deferred, then fetch and process that older step while this one
        runs. This step stays unfetched in its turn unless it cannot be
        run ahead of (_runs_ahead). Any failure at the submit with the
        older step unfetched settles that step first and builds this one
        again from what the host then has, as a host-stepped train built
        it (what the older step's emit releases may be what the submit
        lacked); a failure with nothing unfetched takes the handlers it
        always took. False: the admission is over (requeued or failed)."""
        observing = self._observing
        t0 = time.perf_counter() if observing else 0.0
        t_build = time.perf_counter_ns() if observing else 0
        phases: dict | None = {} if observing else None
        chunked = [it["done"] for it in pending]
        with phase_span(phases, "build", "sched.mixed.build"):
            rows, n_decode, context = self._build_mixed_rows(pending)
        prev = self._mixed_inflight
        try:
            t_submit = time.perf_counter_ns() if observing else 0
            handle = self.engine.mixed_step_submit(rows, phases=phases)
        except Exception as e:
            if prev is not None:
                for item, done in zip(pending, chunked):
                    item["done"] = done
                if self._settle_mixed():
                    return self._mixed_step(pending, prep)
                e = RuntimeError("mixed step in flight failed")  # its slots are failed already
            if (isinstance(e, OutOfPagesError) and self.preempt_max
                    and getattr(e, "recoverable", True) and self._slots):
                # Same ISSUE 7 semantics as bucketed admission:
                # transient pressure REQUEUES the still-admitting
                # requests (head of queue, page-wait latch) — and
                # when the starved span belongs to an ACTIVE
                # decode row, the preemption path may deschedule
                # the youngest instead of failing anyone.
                self._requeue_admission(
                    [it["req"] for it in pending],
                    [it["slot"] for it in pending])
                tag = getattr(e, "slot", None)
                if tag is not None and tag in self._slots:
                    self._fail_after_decode_error(e)
                return False
            self._fail_mixed_admission(pending, e)
            return False
        tokens_from = self._count_submit(any(r.from_block for r in rows[:n_decode]))
        spans: dict = {}
        if observing:
            spans = self._stamp_dispatch("mixed", t_submit, t_build)
            spans["tokens_from"] = tokens_from
            if prep is not None:
                spans["prep"] = {k: round(v, 4) for k, v in prep.items()}
        # The rows this step samples a token for, by identity. A prompt
        # whose last chunk this is becomes an active slot now, its first
        # token on the way (abort_all finds it in _slots from here on).
        states = {row.slot: self._slots[row.slot] for row in rows[:n_decode]}
        first = []
        for item in pending:
            req, slot = item["req"], item["slot"]
            if item["done"] >= len(req.prompt_ids):
                st = _SlotState(req, pos=len(req.prompt_ids), pending_token=_TOKEN_PENDING,
                                pending_logprob=0.0, generated=req.resume_generated + 1,
                                seq=next(self._admit_seq))
                self._slots[slot] = states[slot] = st
                first.append((req, slot, st))
                item["registered"] = True
        step = _MixedInflight(handle, rows, n_decode, states, first, t0, context,
                              phases=phases, spans=spans, behind=prev is not None)
        self._mixed_inflight = step
        if prev is not None and not self._process_mixed(prev):
            self._fail_mixed_admission(pending, RuntimeError("mixed step in flight failed"))
            return False
        # Behind this dispatch: what waited for one (the chunk an admission
        # drained), and the rows of the older step that go on decoding.
        self._deliver_deferred(spans if observing else None, phases)
        if self._runs_ahead(step):
            self._retire_foreseen(step)
        elif not self._settle_mixed():
            self._fail_mixed_admission(pending, RuntimeError("mixed step failed"))
            return False
        return True

    def _retire_foreseen(self, step: _MixedInflight) -> None:
        """The finishes the host can foresee, decided when the step is
        dispatched (ISSUE 40): a decode row whose token from this step is
        its request's last by the budget. Its slot, pages and bias row go
        back NOW, beside the step in flight, so the next step's own
        admission can take the slot (the device runs the programs in
        order: the new occupant's chunk is written after this step has
        read the old one's pages); the state waits in _retiring for its
        last token, delivered by identity when the step is processed."""
        for row in step.rows[:step.n_decode]:
            st = step.states[row.slot]
            if (self._slots.get(row.slot) is st
                    and self._last_by_budget(st.req, st.pos + 1, st.generated + 1)):
                with cause_span("sched.release"):
                    self._retiring.append(st)  # before it leaves _slots: abort_all must find it in one
                    step.retired[row.slot] = st
                    del self._slots[row.slot]
                    self._release_guarded(row.slot, "length", frozen=True)

    def _settle_mixed(self) -> bool:
        """Fetch and process the unfetched mixed step, if there is one:
        what every path that needs the host's state whole calls first (a
        failed submit's handlers, a preemption, the bucketed admission,
        the drain, the loop's end). False if the device failed under it
        (its slots are failed then)."""
        step, self._mixed_inflight = self._mixed_inflight, None
        return step is None or self._process_mixed(step)

    def _process_mixed(self, step: _MixedInflight) -> bool:
        """Fetch a dispatched mixed step's block and stream it out: decode
        rows advance exactly one token, same emission contract as one
        step of a fused chunk; rows whose prompt ended here get their
        first token. A row is emitted only into the state that was
        snapshotted when the step was built (identity). A decode row
        whose state has left its slot meanwhile, on a finish the host
        could not foresee when this step was dispatched behind the one
        that held it (EOS, a stop id, a stop string, a client gone),
        computed a token for a stream that had ended: discarded and
        counted, never delivered."""
        observing = self._observing and step.phases is not None
        phases = step.phases if observing else None
        # Dispatched behind an unfetched step, or fetched with a program
        # behind it: the record runs from here, its dispatch is ``submit_ts``.
        overlapped = step.behind or bool(self._handles) or self._mixed_inflight is not None
        t0 = time.perf_counter() if overlapped else step.t0
        try:
            toks, logprobs = self.engine.mixed_step_fetch(step.handle)
        except Exception as e:
            # The device failed under it: whatever was dispatched behind
            # it is lost too, and every stream it carried ends in "error".
            lost, self._mixed_inflight = self._mixed_inflight, None
            self._handles.clear()
            self.engine._dev_carry = None
            for st in list(step.retired.values()) + list(lost.retired.values() if lost else ()):
                self._retiring = [x for x in self._retiring if x is not st]
                self._fail_request(st.req)
            self._fail_after_decode_error(e)
            return False
        self.last_step_time = self.clock.now()
        self.steps_completed += 1
        if observing:
            self._stamp_fetch()
        emitted = 0
        # A drafting model's step (ISSUE 56): (2, S) tokens and how many of them a
        # row emitted; every other model's: one token a row.
        counts = getattr(step.handle, "counts", None)
        if counts is not None:
            self._count_rounds(step.handle.stats)
        else:
            toks, logprobs = toks[None], logprobs[None]
        with phase_span(phases, "emit", "sched.emit"):
            for row in step.rows[:step.n_decode]:
                st = step.states[row.slot]
                retired = step.retired.get(row.slot) is st
                if not retired and self._slots.get(row.slot) is not st:
                    self._wasted(WASTE_OVERSHOOT, len(row.token_ids))
                    continue
                sent, finished, reason = self._emit_round(st, toks[:, row.slot], logprobs[:, row.slot],
                                                          1 if counts is None else int(counts[row.slot]))
                emitted += sent
                if retired:  # finished by construction: its slot went back at dispatch
                    self._retiring = [x for x in self._retiring if x is not st]
                    self._flush_emits(st.req)
                elif finished:
                    self._finish(row.slot, st.req, reason)
                else:
                    # A row that goes on decoding delivers behind a
                    # dispatch: the one this step was processed behind
                    # (the caller delivers as soon as this returns), else
                    # the next (the next mixed step or run()'s fresh
                    # chunk). Finished rows and first tokens leave at once.
                    self._flush_emits(st.req, defer=True)
            for req, slot, st in step.first:
                if self._slots.get(slot) is not st:
                    continue  # failed or preempted while its first token was on the way
                self.engine.metrics["prefill_batches"] += 1
                st.pending_token = int(toks[0, slot])
                st.pending_logprob = float(logprobs[0, slot])
                if self.engine.prefix_cache is not None:
                    with self.engine._lock:
                        self.engine.prefix_cache.insert(
                            req.prompt_ids, self.engine.allocator.pages_of(slot))
                emitted += 1
                finished, reason = self._emit(st, st.pending_token, st.pending_logprob)
                if finished:
                    self._finish(slot, req, reason)
                else:
                    self._flush_emits(req)
        if observing:
            spans = step.spans
            spans["phases"] = phases
            spans["h2d_arrays"] = step.handle.h2d_arrays
            spans.update(step.handle.stats or {})  # a mixture's expert counts
            plan = getattr(self.engine, "ragged_attention_plan", None)
            if plan is not None:
                # Tokens of the rows the ragged kernel walks in wide
                # tiles (ISSUE 34), and the pages
                # those tiles walk and mask (ISSUE 44): known from the
                # rows packed, no device read.
                wide = [row for row in step.rows[step.n_decode:]
                        if len(row.token_ids) >= plan["wide_from"]]
                spans["attn_wide_tokens"] = sum(len(row.token_ids) for row in wide)
                spans["attn_pages_walked"], spans["attn_pages_masked"] = self.engine.attn_pages(wide)
            if getattr(self.engine, "pool_write_plan", None) is not None:
                # The copies a pool takes for the step's rows on the run-copy
                # path (ISSUE 62); rows written over copies is the run length.
                copies = self.engine.write_copies(step.rows)
                if copies is not None:
                    spans["write_copies"] = copies
            if not overlapped:
                spans.pop("submit_ts", None)
            if counts is not None:
                spans["rows"] = step.n_decode
            self._record_step(
                "mixed", t0, n_steps=1, batch=len(step.rows),
                tokens=emitted, context_tokens=step.context, spans=spans)
        return True

    def _submit_chunk(self, chain: bool) -> "_Inflight | None":
        """Dispatch one fused decode chunk without waiting for it.

        Chained submits take tokens from the engine's device-resident
        carry (host token state may be a chunk stale and freshly
        admitted slots' tokens may still be prefill futures — exactly
        why ``tokens`` is ignored in chained mode); positions are
        *predicted* as last-processed + the steps of any in-flight chunk
        that includes the slot, which is deterministic because every
        active slot advances one token per step. The prediction only
        pre-allocates KV pages for slots that turn out to finish
        mid-flight, whose pages are reclaimed on release. Failures are
        attributed and survive as in the synchronous path.
        """
        # A request that arrived after run()'s want_admit check would
        # otherwise wait out this whole chunk before prefill; skip the
        # submit so the next loop iteration admits first (the
        # pre-pipelining code bounded admission latency the same way by
        # shrinking the chunk to one step). A page-blocked admission
        # (_admit_ready False) must NOT defer the chunk — decode progress
        # is what frees the pages it is waiting for.
        if self._admission_due():
            return None
        n = self.engine.config.decode_chunk
        observing = self._observing
        if chain:
            # Host-free steady state (ISSUE 14): the device carry holds
            # tokens, positions, stop state, budgets, grammar states,
            # and the rng; the engine's host mirror holds the page
            # horizon. NOTHING is assembled here — this branch must stay
            # free of per-slot loops and host-array construction
            # (graftlint jax-hot-path chain-steady scope).
            t_submit = time.perf_counter_ns() if observing else 0
            try:
                handle = self.engine.decode_chunk_submit(
                    None, None, None, None, None, n_steps=n, chain=True,
                    phases={"build": 0.0} if observing else None)
            except Exception as e:
                self._fail_after_decode_error(e)
                return None
            return _Inflight(handle, dict(self._slots), n,
                             self._stamp_dispatch("decode", t_submit, t_submit)
                             if observing else None)
        S = self.engine.config.max_slots
        t_build = time.perf_counter_ns() if observing else 0
        phases: dict | None = {} if observing else None
        with phase_span(phases, "build", "sched.chunk.build"):
            chunk_handles = [h for h in self._handles if isinstance(h, _Inflight)]
            tokens = np.zeros((S,), np.int32)
            positions = np.zeros((S,), np.int32)
            active = np.zeros((S,), bool)
            temps = np.zeros((S,), np.float32)
            top_ps = np.ones((S,), np.float32)
            seeds = np.zeros((S,), np.int32)
            use_seed = np.zeros((S,), bool)
            mstates = np.zeros((S,), np.int32)
            stop_tables = np.full((S, STOP_TABLE_WIDTH), -1, np.int32)
            budgets = np.zeros((S,), np.int64)
            # Rows whose pending token the train's last mixed step holds,
            # unfetched (ISSUE 40): the chunk reads it on the device, at the
            # position and budget the slot has once that step is processed.
            from_block = np.zeros((S,), bool)
            eos_id = getattr(self.engine, "_eos_id", None)
            max_pos = self.engine.config.max_seq_len - 1
            per = getattr(self.engine, "round_tokens", 1)
            for slot, st in self._slots.items():
                # Only chunks carrying THIS request (state identity, not slot
                # id) advance its predicted position — a chunk still in
                # flight for the slot's previous occupant must not.
                inflight_steps = sum(h.n_steps for h in chunk_handles
                                     if h.states.get(slot) is st)
                lag = self._ahead(slot, st)
                if lag is not None:
                    if self._last_by_budget(st.req, st.pos + lag, st.generated + lag):
                        continue  # the token on the device is its last: no row, no pages ahead
                    from_block[slot] = True
                    inflight_steps += lag
                tokens[slot] = max(st.pending_token, 0)
                # (A drafting model moves up to two positions a round: the farthest the
                # row can have come, for the page horizon; the device holds where it is.)
                positions[slot] = min(st.pos + per * inflight_steps, max_pos)
                active[slot] = True
                temps[slot] = st.req.temperature
                top_ps[slot] = st.req.top_p
                if st.req.seed is not None:
                    seeds[slot] = int(st.req.seed)
                    use_seed[slot] = True
                if st.req.grammar is not None:
                    # Host mirror is authoritative here: chain=False submits
                    # only happen after a drain, when every emitted token has
                    # been fed (chained submits take the device carry).
                    mstates[slot] = st.req.grammar.global_state
                stop_tables[slot] = build_stop_row(eos_id, st.req.stop_token_ids)
                budgets[slot] = max(st.req.max_tokens - st.generated - (lag or 0), 0)
        t_submit = time.perf_counter_ns() if observing else 0
        try:
            handle = self.engine.decode_chunk_submit(
                tokens, positions, active, temps, top_ps, n_steps=n,
                seeds=seeds, use_seed=use_seed, mstates=mstates,
                stop_tables=stop_tables, budgets=budgets, phases=phases,
                from_block=from_block)
        except Exception as e:
            if self._mixed_inflight is not None:
                # The train's last step first: fetch and process it, then
                # this submit again from what the host then has, as behind
                # a host-stepped train (a finish it emits may free what the
                # submit lacked).
                self._settle_mixed()
                return self._submit_chunk(chain=False)
            self._fail_after_decode_error(e)
            return None
        tokens_from = self._count_submit(bool(from_block.any()))
        submit = self._stamp_dispatch("decode", t_submit, t_build) if observing else None
        if submit is not None:
            submit["tokens_from"] = tokens_from
        return _Inflight(handle, dict(self._slots), n, submit)  # run() delivers behind it

    def _spec_step(self) -> None:
        """One speculative round: emits 1..K+1 tokens per live slot.

        Per-slot bookkeeping follows serving/speculative.py's invariants:
        st.pos is the pending token's position P, st.draft_len the draft
        cache's valid length D, st.catchup the ≤2 emitted tokens the
        draft hasn't ingested (P == D + len(catchup) - 1 always).
        """
        S = self.engine.config.max_slots
        K = self.engine.config.spec_k
        catchup = np.zeros((S, 2), np.int32)
        catchup_len = np.ones((S,), np.int32)
        catchup_pos = np.zeros((S,), np.int32)
        active = np.zeros((S,), bool)
        temps = np.zeros((S,), np.float32)
        top_ps = np.ones((S,), np.float32)
        seeds = np.zeros((S,), np.int32)
        use_seed = np.zeros((S,), bool)
        mstates = np.zeros((S,), np.int32)
        for slot, st in self._slots.items():
            cu = st.catchup
            catchup[slot, : len(cu)] = cu
            catchup_len[slot] = len(cu)
            catchup_pos[slot] = st.draft_len
            active[slot] = True
            temps[slot] = st.req.temperature
            top_ps[slot] = st.req.top_p
            if st.req.seed is not None:
                seeds[slot] = int(st.req.seed)
                use_seed[slot] = True
            if st.req.grammar is not None:
                mstates[slot] = st.req.grammar.global_state

        observing = self._observing
        t0 = time.perf_counter() if observing else 0.0
        ctx = sum(st.pos for st in self._slots.values()) if observing else 0
        before_emitted = self.spec_emitted
        # A speculative round is synchronous: its dispatch is stamped as
        # the engine call begins, its fetch as it returns.
        spans = self._stamp_dispatch("spec", time.perf_counter_ns()) if observing else None
        out, logprobs, counts = self.engine.spec_round(
            catchup, catchup_len, catchup_pos, active, temps, top_ps,
            seeds=seeds, use_seed=use_seed, mstates=mstates)
        if observing:
            self._stamp_fetch()
        self.last_step_time = self.clock.now()
        self.steps_completed += 1
        self.spec_rounds += 1
        self.spec_slot_rounds += len(self._slots)
        batch = len(self._slots)

        for slot in list(self._slots):
            st = self._slots[slot]
            n = int(counts[slot])
            P = st.pos
            finished = False
            delivered = 0
            for j in range(n):
                st.pos += 1
                st.pending_token = int(out[slot, j])
                st.pending_logprob = float(logprobs[slot, j])
                st.generated += 1
                # Counted per token actually DELIVERED (a finished
                # request's trailing accepted tokens are discarded and
                # must not inflate the acceptance telemetry).
                self.spec_emitted += 1
                delivered += 1
                finished, reason = self._emit(st, st.pending_token, st.pending_logprob)
                if finished:
                    del self._slots[slot]
                    self._release_guarded(slot, reason)
                    break
            # The verify forward ran K+1 positions: the target
            # rejected K+1-n of them, and accepted tokens past a
            # finish are discarded (ISSUE 6 wasted-work attribution).
            self._wasted(WASTE_SPEC_REJECTED, K + 1 - n)
            self._wasted(WASTE_CHUNK_OVERRUN, n - delivered)
            if not finished:
                st.draft_len = P + min(n, K)
                st.catchup = tuple(int(t) for t in out[slot, max(n - 2, 0):n]) \
                    if n == K + 1 else (int(out[slot, n - 1]),)
            if n:
                self._flush_emits(st.req)
        if observing:
            self._record_step("spec", t0, n_steps=1, batch=batch,
                              tokens=self.spec_emitted - before_emitted,
                              context_tokens=ctx, spans=spans)

    def _spec_step_ngram(self) -> None:
        """One prompt-lookup round: host proposes K continuation tokens
        per slot from its own stream (ngram_propose); the engine
        verifies all of them in ONE target forward and emits 1..K+1
        tokens per slot. Bookkeeping is simpler than the model-draft
        path: there is no draft cache, so st.pos is just the pending
        token's position and st.history the emitted stream."""
        S = self.engine.config.max_slots
        K = self.engine.config.spec_k
        pending = np.zeros((S,), np.int32)
        positions = np.zeros((S,), np.int32)
        draft = np.zeros((S, K), np.int32)
        active = np.zeros((S,), bool)
        temps = np.zeros((S,), np.float32)
        top_ps = np.ones((S,), np.float32)
        seeds = np.zeros((S,), np.int32)
        use_seed = np.zeros((S,), bool)
        mstates = np.zeros((S,), np.int32)
        for slot, st in self._slots.items():
            pending[slot] = st.pending_token
            positions[slot] = st.pos
            proposal = ngram_propose(st.history, K)
            if st.req.grammar is not None:
                # Repair prompt-lookup proposals against the automaton
                # (ISSUE 13): a grammar-impossible proposal would be
                # rejected by the masked verify anyway; repairing keeps
                # the acceptance rate up on constrained streams.
                proposal = st.req.grammar.filter_proposal(proposal)
                mstates[slot] = st.req.grammar.global_state
            draft[slot] = proposal
            active[slot] = True
            temps[slot] = st.req.temperature
            top_ps[slot] = st.req.top_p
            if st.req.seed is not None:
                seeds[slot] = int(st.req.seed)
                use_seed[slot] = True

        observing = self._observing
        t0 = time.perf_counter() if observing else 0.0
        ctx = sum(st.pos for st in self._slots.values()) if observing else 0
        before_emitted = self.spec_emitted
        spans = (self._stamp_dispatch("spec_ngram", time.perf_counter_ns())
                 if observing else None)
        out, logprobs, counts = self.engine.spec_round_ngram(
            pending, positions, draft, active, temps, top_ps,
            seeds=seeds, use_seed=use_seed, mstates=mstates)
        if observing:
            self._stamp_fetch()
        self.last_step_time = self.clock.now()
        self.steps_completed += 1
        self.spec_rounds += 1
        self.spec_slot_rounds += len(self._slots)
        batch = len(self._slots)

        for slot in list(self._slots):
            st = self._slots[slot]
            n = int(counts[slot])
            delivered = 0
            for j in range(n):
                st.pos += 1
                st.pending_token = int(out[slot, j])
                st.pending_logprob = float(logprobs[slot, j])
                st.generated += 1
                self.spec_emitted += 1
                delivered += 1
                st.history.append(st.pending_token)
                finished, reason = self._emit(st, st.pending_token, st.pending_logprob)
                if finished:
                    del self._slots[slot]
                    self._release_guarded(slot, reason)
                    break
            self._wasted(WASTE_SPEC_REJECTED, K + 1 - n)
            self._wasted(WASTE_CHUNK_OVERRUN, n - delivered)
            if n:
                self._flush_emits(st.req)
        if observing:
            self._record_step("spec_ngram", t0, n_steps=1, batch=batch,
                              tokens=self.spec_emitted - before_emitted,
                              context_tokens=ctx, spans=spans)

    # Timeline failure damping (ISSUE 6 satellite): log the 1st and every
    # 50th consecutive failure, give up entirely after 8 in a row.
    _TIMELINE_LOG_EVERY = 50
    _TIMELINE_MAX_FAILURES = 8

    @property
    def _observing(self) -> bool:
        """Whether the timeline is attached — the single hot-path gate
        for t0 stamping and context-token summing."""
        return self.timeline is not None

    def _stamp_dispatch(self, kind: str, dispatch_t0: int, submit_t0: int | None = None) -> dict:
        """One dispatch just returned (ISSUE 14 satellite, generalised by
        ISSUE 24 to every kind). Returns what its step record carries:
        ``host_gap_ms``, wall time from the end of the last device
        interaction to ``dispatch_t0``, the moment the engine call began —
        what the device would have idled if the pipeline were depth 1
        (also fed to the engine.host_gap_ms histogram);
        ``inflight_at_dispatch``, the programs dispatched and not yet
        fetched before this one; ``starved_ms``, when that was none and
        work was pending, the time since it fell to none — the device had
        nothing queued and the scheduler had work: the host starved it;
        ``starved_by`` (ISSUE 38), where that is more than nothing, the
        same milliseconds by the cause the thread was in (the cursor's
        ledger, closed here at the same reading: it sums to
        ``starved_ms``); and, for a dispatch whose record is written
        later, ``submit_ts`` (``submit_t0`` on the span clock). Times are
        perf_counter_ns."""
        now = time.perf_counter_ns()
        inflight = len(self._handles) + (self._mixed_inflight is not None)
        spans: dict[str, Any] = {"inflight_at_dispatch": inflight, "starved_ms": 0.0}
        by_cause = self._cursor.stop(now)
        if inflight == 0 and self._starve_t0 is not None:
            spans["starved_ms"] = round((now - self._starve_t0) / 1e6, 4)
            if spans["starved_ms"] > 0:
                spans["starved_by"] = {k: round(ns / 1e6, 4) for k, ns in by_cause.items() if ns >= 50}
        self._starve_t0 = None
        if self._dev_touch is not None:
            gap_ms = max(dispatch_t0 - self._dev_touch, 0) / 1e6
            spans["host_gap_ms"] = round(gap_ms, 4)
            if self.timeline is not None:
                try:
                    self.timeline.record_host_gap(kind, gap_ms)
                except Exception:
                    pass
        self._dev_touch = now
        if submit_t0 is not None:
            spans["submit_ts"] = span_clock_ns(submit_t0) / 1e9
        return spans

    def _stamp_fetch(self) -> None:
        """One fetch just materialized: the host-gap clock restarts, and
        if nothing else is in flight while work is pending, so do the
        starvation clock and its ledger by cause (a fetched handle has
        already left _handles, a mixed step _mixed_inflight)."""
        now = time.perf_counter_ns()
        self._dev_touch = now
        if (not self._handles and self._mixed_inflight is None
                and (self._slots or self._waiting or self._admitting)):
            self._starve_t0 = now
            self._cursor.start(now)

    def _record_step(self, kind: str, t0: float, *, n_steps: int, batch: int,
                     tokens: int, context_tokens: int = 0,
                     spans: dict | None = None) -> None:
        """One decode-timeline record (ISSUE 4): duration covers fetch +
        host-side emission — the full per-step cost a request observes.
        kv_utilization/queue_depth reads are GIL-atomic, lock-free.

        A failing observer must never spam the log once per engine step
        forever (the pre-ISSUE-6 behavior): consecutive failures are
        rate-limited, and after _TIMELINE_MAX_FAILURES in a row the
        timeline and the observatory are detached — serving continues,
        observability reports its own death exactly once."""
        duration = time.perf_counter() - t0
        with cause_span("sched.record"):  # the instruments' own cost, charged to them
            if n_steps > 0:
                # Smoothed per-engine-step wall time: the hang watchdog's
                # deadline base (ISSUE 7). EWMA over per-step cost so a
                # fused chunk and a single prefill weigh comparably.
                per_step = duration / n_steps
                self.step_ewma = per_step if self.step_ewma <= 0 else (
                    0.8 * self.step_ewma + 0.2 * per_step)
            try:
                cost = None
                if self.observatory is not None:
                    # Recompile-stall attribution (ISSUE 19): a ledger delta
                    # since the last record means THIS step paid the compile
                    # wall time. Enrich the timeline record and say so — the
                    # p99 spike and its cause land in the same row.
                    seen = self.observatory.ledger.recompile_count()
                    if seen != self._recompiles_seen:
                        cost = {"recompiled": seen - self._recompiles_seen}
                        self._recompiles_seen = seen
                        self.logger.warn(
                            "engine step stalled on steady-state recompile",
                            "kind", kind, "recompiles", cost["recompiled"],
                            "step_ms", round(duration * 1e3, 1))
                if self.timeline is not None:
                    spans = {**(spans or {}), "context_tokens": context_tokens}
                    if spans.get("phases"):
                        spans["phases"] = {k: round(v, 4) for k, v in spans["phases"].items()}
                    else:
                        spans.pop("phases", None)
                    self.timeline.record(
                        kind, duration, n_steps=n_steps, batch=batch,
                        tokens=tokens, kv_utilization=self.engine.kv_utilization(),
                        queue_depth=self.queue_depth, cost=cost, spans=spans)
                self._timeline_failures = 0
            except Exception as e:
                self._timeline_failures += 1
                n = self._timeline_failures
                if n >= self._TIMELINE_MAX_FAILURES:
                    self.logger.error(
                        "timeline disabled after repeated record failures",
                        e, "consecutive", n)
                    self.timeline = None
                    self.observatory = None
                elif n == 1 or n % self._TIMELINE_LOG_EVERY == 0:
                    self.logger.error("timeline record failed", e, "consecutive", n)

    def _wasted(self, reason: str, tokens: int) -> None:
        """Count tokens the engine computed that no client will see."""
        if tokens > 0:
            self.wasted[reason] += tokens

    def _process_chunk(self, inf: "_Inflight", defer: bool = False) -> None:
        """Fetch a submitted chunk's token block and stream it out.

        Requests that finish mid-chunk have their trailing tokens
        discarded (bounded wasted work); slots admitted after this chunk
        was submitted are excluded by the submit-time snapshot, and a
        slot released + re-admitted mid-flight is excluded by the state
        IDENTITY check — its rows in this chunk belong to the previous
        occupant's (already finished) stream. ``defer``: the caller
        dispatches next and no chunk is behind this one on the device, so
        the rows that go on decoding deliver after that dispatch.
        """
        self._normal_steps += inf.n_steps  # engine steps, for the spec probe cadence
        observing = self._observing
        t0 = time.perf_counter() if observing else 0.0
        # The dict the submit stamped (build, lock_wait, upload_dispatch);
        # the fetch and the emit below add theirs. None when the chunk was
        # submitted with no observer attached.
        phases = getattr(inf.handle, "phases", None) if observing else None
        try:
            toks, logprobs = self.engine.decode_chunk_fetch(inf.handle)
        except Exception as e:
            # The device-side failure poisons the chained carry and
            # every later-submitted handle; all are invalidated so
            # recovery resubmits from host state.
            self.engine._dev_carry = None
            self._handles.clear()
            self._fail_after_decode_error(e)
            return
        self.last_step_time = self.clock.now()
        self.steps_completed += inf.n_steps
        if observing:
            # Fetch N just completed: the clock for "host time between
            # fetching chunk N and chunk N+1's dispatch" starts here.
            self._stamp_fetch()

        ctx = sum(s.pos for s in inf.states.values()) if observing else 0
        emitted = 0
        overrun = 0
        # A drafting model's chunk (ISSUE 56): two rows of the block a round, of which
        # a row emitted ``counts[round, slot]``.
        counts = getattr(inf.handle, "counts", None)
        per = 1 if counts is None else 2
        if counts is not None:
            self._count_rounds(getattr(inf.handle, "stats", None))
        with phase_span(phases, "emit", "sched.emit"):
            for slot, snap_st in inf.states.items():
                st = self._slots.get(slot)
                if st is not snap_st:
                    # Finished, failed, or re-admitted mid-flight: every row
                    # this chunk computed for the slot served a stream that
                    # already ended (bounded wasted work by design — now
                    # *attributed*, ISSUE 6). If the finish was one the
                    # DEVICE also detected (ISSUE 14), the early-exit carry
                    # froze the row before this chunk sampled anything for
                    # it — nothing was wasted, so nothing is billed.
                    if not snap_st.device_stopped:
                        overrun += toks.shape[0]
                    continue
                slot_emitted = emitted
                for j in range(toks.shape[0]):
                    if counts is not None and j % per >= counts[j // per, slot]:
                        continue
                    st.pos += 1
                    st.pending_token = int(toks[j, slot])
                    st.pending_logprob = float(logprobs[j, slot])
                    st.generated += 1
                    emitted += 1
                    if self.engine.spec_ngram:
                        # Keep prompt-lookup history fresh while adaptive
                        # speculation is parked in the normal loop, so a
                        # probe's proposals see the full stream.
                        st.history.append(st.pending_token)
                    finished, reason = self._emit(st, st.pending_token, st.pending_logprob)
                    if finished:
                        self._finish(slot, st.req, reason, frozen=st.device_stopped)
                        if not st.device_stopped:
                            # Device-detected finishes froze the row at this
                            # very step (ISSUE 14): the trailing block is
                            # repeats, not computed tokens — zero overrun.
                            overrun += toks.shape[0] - per * (j // per + 1)
                        break
                if emitted > slot_emitted and slot in self._slots:
                    # One flush per request per CHUNK: a pipelined
                    # decode_chunk's whole token block reaches the event
                    # loop as one wakeup instead of n_steps of them (a
                    # stream that finished was flushed by its _finish).
                    self._flush_emits(st.req, defer=defer)
        self._wasted(WASTE_CHUNK_OVERRUN, overrun)
        if observing:
            self._record_step("decode", t0, n_steps=inf.n_steps,
                              batch=len(inf.states), tokens=emitted,
                              context_tokens=ctx,
                              spans={**(inf.submit or {}), "phases": phases,
                                     **({"rows": len(inf.states)} if counts is not None else {}),
                                     "h2d_arrays": getattr(inf.handle, "h2d_arrays", 0),
                                     # a mixture's experts_read, from the fetch block
                                     **(getattr(inf.handle, "stats", None) or {})})

    def _emit_round(self, st: _SlotState, toks, logprobs, count: int) -> tuple[int, bool, str | None]:
        """A mixed step's tokens for one decoding row, in order: one, or from a
        model that drafts for itself (ISSUE 56) one or two; a finish at the first
        discards the second. Returns (tokens sent, finished, reason)."""
        finished, reason = False, None
        for j in range(count):
            st.pos += 1
            st.pending_token = int(toks[j])
            st.pending_logprob = float(logprobs[j])
            st.generated += 1
            finished, reason = self._emit(st, st.pending_token, st.pending_logprob)
            if finished:
                return j + 1, finished, reason
        return count, finished, reason

    def _count_rounds(self, stats: dict | None) -> None:
        """A drafting model's step, as its program counted it (ISSUE 56; ``mixtral.MTP_STATS`` of
        the fetch block): the counters /metrics and /debug/status serve, and the
        refused drafts' positions as wasted work."""
        drafted, accepted = int((stats or {}).get("mtp_drafted", 0)), int((stats or {}).get("mtp_accepted", 0))
        self.spec_rounds += 1
        self.spec_slot_rounds += drafted
        self.spec_accepted += accepted
        self.spec_emitted += int((stats or {}).get("mtp_tokens", 0))
        self._wasted(WASTE_SPEC_REJECTED, drafted - accepted)

    def _finish(self, slot: int, req: GenRequest, reason: str | None,
                frozen: bool = False) -> None:
        """A stream's last step on the normal path: the slot leaves the
        table, its pages and grammar span go back, and its last tokens are
        flushed at once. One cause of the idle ledger, ``release`` (the
        structured runtime's part is ``release_slot`` inside it)."""
        with cause_span("sched.release"):
            del self._slots[slot]
            self._release_guarded(slot, reason, frozen=frozen)
            self._flush_emits(req)

    def _release_guarded(self, slot: int, reason: str | None,
                         frozen: bool = False) -> None:
        """Release on the normal finish path: an allocator bookkeeping
        error must fail at most this slot's cleanup, never the scheduler
        thread (the invariant the pre-pipelining loop guarded with its
        decode-step try/except; code-review round 3). ``frozen`` relays
        whether the device already froze the row (ISSUE 14) so the
        common finish path skips the carry patch."""
        try:
            self._release(slot, reason, frozen=frozen)
        except Exception as e:
            self.logger.error("slot release failed on finish", e, "slot", slot)

    def _emit(self, st: _SlotState, token: int, logprob: float) -> tuple[bool, str | None]:
        """Send one token to the request's callback; decide termination."""
        req = st.req
        if "first_token" not in req.phase_ns:
            req.phase_ns["first_token"] = time.time_ns()  # prefill ends
        eos = self.engine.tokenizer.eos_token_id
        is_stop = token == eos or token in req.stop_token_ids
        # Grammar host mirror (ISSUE 13): every emitted token advances
        # the session. "end" means the grammar already finished (or the
        # token is impossible under it — a fused chunk decoding past the
        # completion point): terminate HERE with the stop contract, so
        # the token carries no content and the emitted text is exactly
        # the grammar-complete document.
        grammar_end = False
        if req.grammar is not None:
            if req.grammar.feed(token) == "end":
                is_stop = grammar_end = True
        hit_max = st.generated >= req.max_tokens
        out_of_room = st.pos + 1 >= self.engine.config.max_seq_len
        finished = is_stop or hit_max or out_of_room
        reason = None
        if finished:
            reason = "stop" if is_stop else "length"
            # On-device stopping (ISSUE 14): did the chunk's carry freeze
            # this row at the same step? True for every finish criterion
            # the device enforces — a stop token that fit the shipped
            # table (EOS rides the table via engine._eos_id — the SAME
            # source the device was armed from, so an engine that
            # couldn't ship EOS never overclaims a freeze), grammar
            # completion, max_tokens, cache-row exhaustion. False only
            # for host-side backstops (stop strings at the serving edge
            # arrive as `disconnected`, handled below), so
            # _process_chunk knows whether trailing chunk rows were
            # computed or frozen.
            st.device_stopped = (
                hit_max or out_of_room or grammar_end
                or (is_stop and int(token) in self._device_stop_ids(req)))
        if req.disconnected and not finished:
            # Early termination (ISSUE 7): the client abandoned the
            # stream — finish at this decode step and free the slot/KV
            # pages instead of decoding to max_tokens. Tokens already
            # decoded keep their ISSUE 6 wasted-work attribution below.
            finished = True
            reason = "disconnected"
        if finished:
            req.phase_ns.setdefault("finish", time.time_ns())  # decode ends
        if self.preempt_max:
            # Preemption resume material: a descheduled request re-enters
            # admission with prompt + out_tokens as its new prompt.
            st.out_tokens.append(token)
        try:
            req.callback(token, logprob, finished, reason)
        except Exception:
            # A dead client must not kill the batch — and a callback
            # that raises IS a dead client: mark the stream disconnected
            # so the next emission terminates it instead of silently
            # decoding to max_tokens forever (ISSUE 7 satellite).
            req.disconnected = True
        if req.disconnected:
            # The serving edge marked the stream abandoned; nobody reads
            # these tokens (ISSUE 6 wasted-work attribution).
            self._wasted(WASTE_DISCONNECTED, 1)
        return finished, reason

    def _device_stop_ids(self, req: GenRequest) -> set:
        """The subset of the request's stop ids that fit its on-device
        stop row (EOS first, then sorted ids, STOP_TABLE_WIDTH wide) —
        a finish on any other stop id was a host-only detection the
        device over-ran."""
        eos = getattr(self.engine, "_eos_id", None)
        row = build_stop_row(eos, req.stop_token_ids)
        return {int(t) for t in row if t >= 0}

    def _release(self, slot: int, reason: str | None,
                 frozen: bool = False) -> None:
        self.engine.release_slot(slot, frozen=frozen)  # frees KV pages in paged mode
        with self._wake:
            self._free.append(slot)
            self._wake.notify()


# ----------------------------------------------------------------------
def generate_sync(
    scheduler: Scheduler,
    prompt_ids: list[int],
    max_tokens: int = 64,
    temperature: float = 0.0,
    top_p: float = 1.0,
    stop_token_ids: frozenset[int] = frozenset(),
    timeout: float = 120.0,
    seed: int | None = None,
) -> tuple[list[int], str | None]:
    """Blocking helper used by tests and the non-streaming path."""
    q: queue.Queue = queue.Queue()

    def cb(token: int, logprob: float, finished: bool, reason: str | None) -> None:
        q.put((token, finished, reason))

    scheduler.submit(GenRequest(
        prompt_ids=prompt_ids, max_tokens=max_tokens, temperature=temperature,
        top_p=top_p, stop_token_ids=stop_token_ids, callback=cb, seed=seed,
    ))
    out: list[int] = []
    # Blocking helper for tests/CLI: runs on its own thread against a
    # real queue, so real wall-clock is the point here.
    deadline = time.monotonic() + timeout  # graftlint: disable=clock-discipline
    while True:
        token, finished, reason = q.get(  # graftlint: disable=clock-discipline
            timeout=max(deadline - time.monotonic(), 0.1))
        is_stop_tok = reason == "stop"
        if not (finished and is_stop_tok):
            out.append(token)
        else:
            # stop tokens are not part of the visible completion
            pass
        if finished:
            return out, reason
