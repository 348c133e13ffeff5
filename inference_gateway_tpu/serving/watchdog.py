"""Engine hang watchdog with supervised restart (ISSUE 7 tentpole b).

A wedged device step (driver hang, XLA deadlock)
blocks the scheduler thread inside a fetch forever: requests hold slots,
the queue backs up, and before this module the only defense was the
/health stall flag — an external orchestrator had to kill the whole
process. The watchdog closes the loop in-process:

- **Hang detection** compares the scheduler's ``steps_completed``
  progress counter between checks on an injectable clock (VirtualClock
  in tests — zero real sleeps). No progress with active requests for
  longer than the step deadline declares the engine wedged.
- **The deadline derives from measurement**: ``multiplier`` × the
  scheduler's EWMA per-step wall time (ISSUE 6 ``_record_step``), with
  ``min_deadline`` as an absolute floor: it alone decides before the
  first recorded step, and keeps cold engines and slow CPU runs from
  misfiring after it.
- **Supervised restart**: forensics first (timeline tail + the wedged
  scheduler thread's mid-stall stack, the PR 4 playbook), then the
  sidecar fails every in-flight request with a retryable error, rebuilds
  the ``Engine`` in place on an executor thread, and swaps in a fresh
  scheduler. The sidecar's health flips degraded → ready around the
  window so PR 1 failover pools route elsewhere meanwhile. The wedged
  thread itself is unkillable (CPython) — it is abandoned with ``_stop``
  set and exits if the device call ever returns.
"""

from __future__ import annotations

import asyncio
import sys
import time
import traceback

from inference_gateway_tpu.resilience.clock import MonotonicClock


class EngineWatchdog:
    """Device-step deadline watchdog over a SidecarServer's scheduler.

    Construct, pass to ``SidecarServer(engine_watchdog=...)`` (which
    binds it), and it runs as an asyncio task on the sidecar's loop.
    Tests drive ``check()`` directly on a VirtualClock instead of
    starting the loop.
    """

    def __init__(self, *, interval: float = 1.0, multiplier: float = 20.0,
                 min_deadline: float = 60.0, clock=None, logger=None) -> None:
        self.interval = interval
        self.multiplier = multiplier
        self.min_deadline = min_deadline
        self.clock = clock or MonotonicClock()
        self.logger = logger
        self.sidecar = None  # bound by SidecarServer
        self.trips = 0
        self._task: asyncio.Task | None = None
        self._last_sched = None
        self._last_steps = -1
        self._last_progress = self.clock.now()
        self._restarting = False

    # -- lifecycle -----------------------------------------------------
    def bind(self, sidecar) -> None:
        self.sidecar = sidecar

    def start(self) -> None:
        from inference_gateway_tpu.resilience.clock import VirtualClock

        if isinstance(self.clock, VirtualClock):
            # Zero-sleep tests drive check() directly; a virtual-clock
            # sleep loop would spin the event loop (same auto-disable
            # contract as the PR 4 EventLoopWatchdog).
            return
        if self._task is None:
            self._task = asyncio.get_running_loop().create_task(self._run())

    async def stop(self) -> None:
        if self._task is not None:
            self._task.cancel()
            try:
                await self._task
            except (asyncio.CancelledError, Exception):
                pass
            self._task = None

    async def _run(self) -> None:
        while True:
            await self.clock.sleep(self.interval)
            try:
                await self.check()
            except asyncio.CancelledError:
                raise
            except Exception as e:
                if self.logger is not None:
                    self.logger.error("engine watchdog check failed", e)

    # -- policy --------------------------------------------------------
    def deadline(self) -> float:
        """Seconds without a completed step (while requests are active)
        that declare the engine wedged: ``max(min_deadline, multiplier x
        step_ewma)``, the floor alone until a step has been recorded."""
        est = getattr(self.sidecar.scheduler, "step_ewma", 0.0)
        return max(self.min_deadline, self.multiplier * est)

    def stats(self) -> dict:
        """/debug/status view."""
        return {
            "trips": self.trips,
            "deadline_seconds": round(self.deadline(), 3) if self.sidecar else None,
            "interval": self.interval,
            "multiplier": self.multiplier,
            "min_deadline": self.min_deadline,
            "restarting": self._restarting,
        }

    # -- one check tick ------------------------------------------------
    async def check(self) -> bool:
        """Compare progress since the last tick; trip the supervised
        restart when the step deadline is exceeded with active
        requests. Returns True when a restart was performed."""
        if self.sidecar is None or self._restarting:
            return False
        sched = self.sidecar.scheduler
        now = self.clock.now()
        # The progress signature is a composite: completed steps PLUS
        # the engine's own work counters, so a long multi-chunk prefill
        # (which bumps prefill_tokens per chunk but completes no
        # scheduler step until it returns) reads as alive. A first-use
        # XLA compile is still opaque — SERVING_WATCHDOG_MIN_DEADLINE
        # must exceed the worst cold-compile a deployment expects (the
        # standalone sidecar warms the engine before serving).
        metrics = sched.engine.metrics
        steps = (sched.steps_completed, metrics.get("prefill_tokens", 0),
                 metrics.get("decode_steps", 0), metrics.get("prefill_batches", 0))
        # "Busy" includes QUEUED work, not just registered slots: a
        # prefill that wedges mid-admission leaves its batch in neither
        # _waiting nor _slots (it lives in _admitting), and the /health
        # stall flag is blind to that state too — the watchdog must not
        # be (code-review finding).
        busy = (sched.active_requests() > 0 or sched.queue_depth > 0
                or bool(sched._admitting))
        if sched is not self._last_sched or steps != self._last_steps or not busy:
            self._last_sched = sched
            self._last_steps = steps
            self._last_progress = now
            return False
        if now - self._last_progress <= self.deadline():
            return False
        self.trips += 1
        self._restarting = True
        try:
            forensics = self._forensics(sched, now - self._last_progress)
            if self.logger is not None:
                self.logger.error(
                    "engine step deadline exceeded; supervised restart", None,
                    "stalled_seconds", round(now - self._last_progress, 3),
                    "deadline", round(self.deadline(), 3))
            await self.sidecar.restart_engine("step_deadline_exceeded",
                                              forensics=forensics)
        finally:
            self._restarting = False
            self._last_sched = self.sidecar.scheduler
            self._last_steps = -1
            self._last_progress = self.clock.now()
        return True

    def _forensics(self, sched, stalled_seconds: float) -> dict:
        """What was the engine doing when it wedged: the scheduler
        thread's mid-stall stack (it is blocked *right now* — exactly
        the PR 4 mid-stall-stack playbook) and the timeline tail."""
        out: dict = {
            "stalled_seconds": round(stalled_seconds, 3),
            "active_requests": sched.active_requests(),
            "queue_depth": sched.queue_depth,
            "steps_completed": sched.steps_completed,
            "captured_at": time.time(),  # graftlint: disable=clock-discipline -- epoch forensics stamp
        }
        try:
            th = sched._thread
            frames = sys._current_frames()
            if th is not None and th.ident in frames:
                out["scheduler_stack"] = traceback.format_stack(frames[th.ident])
        except Exception:
            pass
        timeline = getattr(self.sidecar, "timeline", None)
        if timeline is not None:
            try:
                out["timeline_tail"] = timeline.tail(32)
            except Exception:
                pass
        return out
