"""Engine device observatory (ISSUE 19 tentpole).

The observability stack built so far sees everything *except* the
device: the zero-h2d steady state is
proven only by tests (ISSUE 14), and a silent steady-state recompile —
the classic TPU throughput killer — is invisible until someone reads a
profile. This module makes the device boundaries first-class
production telemetry:

- **CompileLedger** — wraps every jitted engine entry point and records
  each compilation: program name, static shape signature and compile
  wall-ms (a compile is seen, never repeated: nothing here lowers a
  program again). Any compile *after* warmup completes is a
  **steady-state recompile**: it increments ``engine.recompiles`` and
  emits a wide event carrying the shape-signature diff that triggered
  it.
- **Live HBM accounting** — ``device.memory_stats()`` (bytes-in-use /
  peak) against the analytic plan (weights + KV pool) plus the KV
  page-pool high-water mark. Framed ``measured: false`` off-TPU —
  never fabricated.
- **Transfer audit** — lightweight h2d/d2h counting on the engine's
  submit/fetch seams as ``engine.transfers{direction,path}``. The PR 14
  invariant becomes a live production metric: chained early-exit
  submits must read ``{direction="h2d", path="chain"} == 0`` on any
  worker's ``/metrics``, any time.

Detection mechanics: each jitted entry point is shadowed on the Engine
*instance* with a wrapper that snapshots ``PjitFunction._cache_size()``
before the call and compares after — a cache-size delta is a compile.
The jit caches are class-level, so two Engine instances in one process
share them; a compile triggered by a sibling instance between this
wrapper's before/after stamps would be mis-attributed. The sidecar owns
exactly one live Engine (restart swaps, never overlaps), so this is a
documented non-issue in production and an accepted caveat in tests.

Everything here is optional and None-gated on the engine hot path: with
``TELEMETRY_DEVICE_ENABLE=false`` no wrapper is installed and every
seam pays one ``is None`` check — the same zero-overhead-off discipline
as the step timeline.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any, Callable

__all__ = [
    "JIT_ENTRY_POINTS",
    "CompileLedger",
    "TransferAudit",
    "DeviceObservatory",
]

# Every jitted Engine entry point the ledger wraps (instance-attribute
# shadowing; the class attribute stays untouched). Names are Engine
# attributes; the ledger label drops the leading underscore.
JIT_ENTRY_POINTS: tuple[str, ...] = (
    "_prefill_fn",
    "_prefill_fn_mm",
    "_prefill_fn_paged",
    "_prefill_chunk_fn",
    "_prefill_chunk_fn_paged",
    "_decode_chunk_fn_ee",
    "_decode_chunk_fn_paged_ee",
    "_fresh_unpack_fn",
    "_mixed_step_fn",
    "_admit_scatter_fn_ee",
    "_draft_prefill_fn",
    "_spec_round_fn",
    "_spec_verify_ngram_fn",
    "_mark_done_fn",
)

def _describe(x: Any) -> str:
    """One argument's contribution to a static shape signature.

    Arrays render as ``dtype[d0,d1]`` (shape/dtype survive donation —
    only the buffer dies); hashable statics render by value, because a
    changed static value IS a recompile trigger and must show in the
    diff."""
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        return f"{dtype}[{','.join(str(d) for d in shape)}]"
    if x is None or isinstance(x, (bool, int, float, str)):
        return repr(x)
    if isinstance(x, (list, tuple)):
        return "(" + ",".join(_describe(e) for e in x) + ")"
    return type(x).__name__


def _signature(args: tuple[Any, ...], kwargs: dict[str, Any]) -> tuple[str, ...]:
    parts = [_describe(a) for a in args]
    parts.extend(f"{k}={_describe(v)}" for k, v in sorted(kwargs.items()))
    return tuple(parts)


def _signature_diff(prev: tuple[str, ...], cur: tuple[str, ...]) -> list[str]:
    """Per-argument diff between two signatures — the wide event's
    payload: exactly which shape/static changed to trigger a recompile."""
    out: list[str] = []
    for i in range(max(len(prev), len(cur))):
        p = prev[i] if i < len(prev) else "<absent>"
        c = cur[i] if i < len(cur) else "<absent>"
        if p != c:
            out.append(f"arg{i}: {p} -> {c}")
    return out


class CompileLedger:
    """Bounded ledger of every XLA compilation the engine performs.

    Thread-safe: the engine lock does NOT cover all wrapped entry
    points (prefill and decode run on different scheduler phases), and
    ``/debug/compile`` snapshots from the serving thread."""

    def __init__(self, *, size: int = 256, otel: Any = None,
                 model: str = "", logger: Any = None,
                 now_fn: Callable[[], float] | None = None) -> None:
        self._lock = threading.Lock()
        self._records: deque[dict[str, Any]] = deque(maxlen=max(size, 1))
        self._recompile_events: deque[dict[str, Any]] = deque(maxlen=32)
        self._last_signature: dict[str, tuple[str, ...]] = {}
        self._fallback_seen: dict[str, set[tuple[str, ...]]] = {}
        self.otel = otel
        self.model = model
        self.logger = logger
        # graftlint clock-discipline: perf_counter is the allowlisted
        # profiling stamp; injectable for deterministic tests.
        self._now: Callable[[], float] = now_fn or time.perf_counter
        self.compiles = 0
        self.recompiles = 0
        self.warmed = False

    # -- wrapping ------------------------------------------------------
    def wrap(self, program: str, fn: Any) -> Callable[..., Any]:
        """Shadow one jitted entry point with compile detection.

        ``_cache_size()`` delta is the primary detector (O(1), no
        tracing); when the attribute is missing (plain function or
        future jax), fall back to signature-set membership — strictly
        weaker (can't see cache evictions) but never wrong about a
        first-seen signature."""
        cache_size = getattr(fn, "_cache_size", None)

        def wrapper(*args: Any, **kwargs: Any) -> Any:
            before = cache_size() if cache_size is not None else -1
            t0 = self._now()
            out = fn(*args, **kwargs)
            wall_ms = (self._now() - t0) * 1e3
            if cache_size is not None:
                if cache_size() != before:
                    self._on_compile(program, args, kwargs, wall_ms)
            else:
                sig = _signature(args, kwargs)
                seen = self._fallback_seen.setdefault(program, set())
                if sig not in seen:
                    seen.add(sig)
                    self._on_compile(program, args, kwargs, wall_ms)
            return out

        wrapper.__name__ = f"observed_{program}"  # aid stack traces
        # NOT __wrapped__: jax's jit wrapper already carries that (via
        # functools.wraps), so it can't double as the idempotency marker.
        setattr(wrapper, "_ledger_inner", fn)
        return wrapper

    def _on_compile(self, program: str, args: tuple[Any, ...],
                    kwargs: dict[str, Any], wall_ms: float) -> None:
        sig = _signature(args, kwargs)
        with self._lock:
            self.compiles += 1
            recompile = self.warmed
            prev = self._last_signature.get(program)
            self._last_signature[program] = sig
            record: dict[str, Any] = {
                "program": program,
                "signature": ", ".join(sig),
                "compile_ms": round(wall_ms, 3),
                "recompile": recompile,
            }
            self._records.append(record)
            event: dict[str, Any] | None = None
            if recompile:
                self.recompiles += 1
                event = {
                    "program": program,
                    "signature": ", ".join(sig),
                    "prev_signature": ", ".join(prev) if prev else "",
                    "diff": _signature_diff(prev or (), sig),
                    "compile_ms": round(wall_ms, 3),
                }
                self._recompile_events.append(event)
        if self.otel is not None:
            try:
                self.otel.record_compile(self.model, program, wall_ms / 1e3,
                                         recompile=recompile)
            except Exception:
                pass
        if event is not None and self.logger is not None:
            try:
                # The wide event: a steady-state recompile is a
                # throughput incident, not a debug curiosity.
                self.logger.warn(
                    "steady-state recompile detected",
                    "program", program,
                    "compile_ms", round(wall_ms, 1),
                    "diff", "; ".join(event["diff"]) or "<new program>",
                    "signature", event["signature"],
                    "prev_signature", event["prev_signature"])
            except Exception:
                pass

    # -- reading -------------------------------------------------------
    def warmup_begin(self) -> None:
        """Open (or re-open) the warmup bracket: compiles are expected
        until mark_warmup_complete(). Engine.warmup() brackets itself so
        a supervised restart's warmup never reads as recompiles."""
        with self._lock:
            self.warmed = False

    def mark_warmup_complete(self) -> None:
        with self._lock:
            self.warmed = True

    def snapshot(self) -> dict[str, Any]:
        with self._lock:
            return {
                "compiles": self.compiles,
                "recompiles": self.recompiles,
                "warmed": self.warmed,
                "programs": {p: ", ".join(s)
                             for p, s in sorted(self._last_signature.items())},
                "records": list(self._records),
                "recompile_events": list(self._recompile_events),
            }

    def recompile_count(self) -> int:
        with self._lock:
            return self.recompiles

    def recent_recompiles(self, n: int) -> list[dict[str, Any]]:
        with self._lock:
            events = list(self._recompile_events)
        return events[-n:] if n > 0 else []


class TransferAudit:
    """h2d/d2h transfer counters keyed by (direction, path).

    Counts the engine's submit/fetch staging events (``count``), the
    host arrays they staged (``arrays``) and those arrays' summed nbytes
    (``bytes``). ``arrays / count`` is how many transfers one submit
    makes: 1 on the packed ``h2d/mixed`` and ``h2d/fresh`` paths
    (ISSUE 25), where ``bytes`` is the packed buffer's true size.
    The load-bearing series is ``("h2d", "chain")``: the early-exit
    chained submit stages nothing, so the audit proves the PR 14
    invariant by *never recording there* — the series is pre-seeded to
    zero so its absence can't be mistaken for its truth."""

    def __init__(self, *, otel: Any = None, model: str = "") -> None:
        self._lock = threading.Lock()
        self._counts: dict[tuple[str, str], dict[str, int]] = {}
        self.otel = otel
        self.model = model

    def seed(self, direction: str, path: str) -> None:
        with self._lock:
            self._counts.setdefault((direction, path),
                                    {"count": 0, "arrays": 0, "bytes": 0})
        if self.otel is not None:
            try:
                self.otel.record_transfer(self.model, direction, path, 0, 0)
            except Exception:
                pass

    def record(self, direction: str, path: str, nbytes: int = 0,
               arrays: int = 1) -> None:
        with self._lock:
            slot = self._counts.setdefault((direction, path),
                                           {"count": 0, "arrays": 0, "bytes": 0})
            slot["count"] += 1
            slot["arrays"] += int(arrays)
            slot["bytes"] += int(nbytes)
        if self.otel is not None:
            try:
                self.otel.record_transfer(self.model, direction, path, 1,
                                          int(nbytes))
            except Exception:
                pass

    def snapshot(self) -> dict[str, dict[str, int]]:
        with self._lock:
            return {f"{d}/{p}": dict(v)
                    for (d, p), v in sorted(self._counts.items())}

    def count(self, direction: str, path: str) -> int:
        with self._lock:
            slot = self._counts.get((direction, path))
            return slot["count"] if slot else 0


class DeviceObservatory:
    """Facade the engine, sidecar, and fleet pane share.

    ``attach(engine)`` installs the compile wrappers and computes the
    analytic HBM plan; the engine then feeds the transfer audit through
    its ``self.observatory`` attribute (None when disabled — one
    attribute check per seam)."""

    def __init__(self, *, otel: Any = None, model: str = "",
                 logger: Any = None, ledger_size: int = 256,
                 now_fn: Callable[[], float] | None = None) -> None:
        self.otel = otel
        self.model = model
        self.ledger = CompileLedger(size=ledger_size, otel=otel,
                                    model=model, logger=logger,
                                    now_fn=now_fn)
        self.transfers = TransferAudit(otel=otel, model=model)
        self._engine: Any = None
        self._plan: dict[str, int] = {}

    # -- wiring --------------------------------------------------------
    def attach(self, engine: Any) -> None:
        """Install compile wrappers on this engine instance and adopt it
        as the HBM accounting subject. Idempotent per engine; a
        supervised restart re-attaches to the replacement (the ledger
        carries over — compiles are a process-lifetime story)."""
        self._engine = engine
        for name in JIT_ENTRY_POINTS:
            fn = getattr(engine, name, None)
            if fn is None:
                continue
            if getattr(fn, "_ledger_inner", None) is not None:
                continue  # already shadowed (re-attach of same engine)
            setattr(engine, name, self.ledger.wrap(name.lstrip("_"), fn))
        engine.observatory = self
        self._plan = self._hbm_plan(engine)
        # Pre-seed the invariant series: "h2d/chain == 0" must be a
        # scrapeable zero, not a missing key.
        self.transfers.seed("h2d", "chain")

    def warmup_begin(self) -> None:
        self.ledger.warmup_begin()

    def mark_warmup_complete(self) -> None:
        self.ledger.mark_warmup_complete()

    # -- transfer seam (called from the engine hot path) ---------------
    def record_transfer(self, direction: str, path: str, nbytes: int = 0,
                        arrays: int = 1) -> None:
        self.transfers.record(direction, path, nbytes, arrays)

    # -- HBM -----------------------------------------------------------
    @staticmethod
    def _hbm_plan(engine: Any) -> dict[str, int]:
        """Analytic device-byte plan from the live engine's own config:
        weights at the serving dtype (matmul weights at the quantized
        width) + the KV pool reservation (+ a state store, + the
        logit-bias table of the structured runtime). Mirrors profiles.hbm_plan's
        pricing but reads the engine, not a named profile — the sidecar
        serves ad-hoc configs too."""
        try:
            from inference_gateway_tpu.serving.profiles import (
                kv_bytes_per_token,
                llama_param_count,
                mixtral_param_count,
            )

            cfg = engine.model_cfg
            econf = engine.config
            dtype_bytes = 2 if econf.dtype == "bfloat16" else 4
            n_params = (mixtral_param_count(cfg) if engine.is_moe
                        else llama_param_count(cfg))
            wq = {"int8": 1.0, "int4": 0.5}.get(econf.quantize or "",
                                                float(dtype_bytes))
            embed = cfg.vocab_size * cfg.hidden_size
            weights = int(embed * dtype_bytes + (n_params - embed) * wq)
            if engine.allocator is not None:
                tokens = engine.allocator.num_pages * econf.page_size
            else:
                tokens = econf.max_slots * econf.max_seq_len
            kv_pool = tokens * kv_bytes_per_token(cfg, dtype_bytes)
            plan = {"weights_bytes": weights, "kv_pool_bytes": kv_pool,
                    "plan_bytes": weights + kv_pool}
            if getattr(engine, "carries_state", False):
                # The per-slot state store beside the pool (float32).
                plan["state_store_bytes"] = econf.max_slots * cfg.state_bytes_per_slot
                plan["plan_bytes"] += plan["state_store_bytes"]
            if getattr(engine, "structured", None) is not None:
                # The per-slot logit-bias table every step program reads
                # (float32, one zero row past the slots: ISSUE 49).
                plan["logit_bias_bytes"] = (econf.max_slots + 1) * cfg.vocab_size * 4
                plan["plan_bytes"] += plan["logit_bias_bytes"]
            return plan
        except Exception:
            return {}

    def hbm_snapshot(self) -> dict[str, Any]:
        """Live vs plan, summed over every device the process holds and
        listed per device — a sharded engine that put everything on
        device 0 shows here. ``measured`` is honest: CPU's
        memory_stats() returns None and the pane says so — live/peak are
        never fabricated from the plan."""
        out: dict[str, Any] = {"measured": False, "plan": dict(self._plan)}
        engine = self._engine
        if engine is not None and engine.allocator is not None:
            alloc = engine.allocator
            high = getattr(alloc, "pages_high_water", 0)
            page_bytes = self._plan.get("kv_pool_bytes", 0) // max(alloc.num_pages, 1)
            out["kv_pages"] = {
                "total": alloc.num_pages,
                "free": alloc.free_page_count(),
                "high_water": high,
                "high_water_bytes": high * page_bytes,
            }
        try:
            import jax

            stats = [(dev.id, dev.memory_stats()) for dev in jax.devices()]
        except Exception:
            stats = []
        per_device = [
            {"id": i, "live_bytes": int(st["bytes_in_use"]),
             "peak_bytes": int(st.get("peak_bytes_in_use", st["bytes_in_use"]))}
            for i, st in stats
        ] if stats and all(st and "bytes_in_use" in st for _i, st in stats) else []
        if per_device:
            out["measured"] = True
            out["per_device"] = per_device
            out["live_bytes"] = sum(d["live_bytes"] for d in per_device)
            out["peak_bytes"] = sum(d["peak_bytes"] for d in per_device)
            plan = self._plan.get("plan_bytes", 0)
            if plan:
                out["live_vs_plan"] = round(out["live_bytes"] / plan, 4)
        else:
            out["note"] = ("device backend exposes no memory_stats() "
                           "(CPU host) — live/peak unavailable, "
                           "plan is analytic")
        return out

    def sample_hbm_gauges(self) -> None:
        """Refresh the engine.hbm.* gauges (called on /metrics scrape).
        Off-TPU only the plan gauge is set — absent live/peak series are
        the honest representation of 'not measured'."""
        if self.otel is None:
            return
        snap = self.hbm_snapshot()
        try:
            self.otel.set_hbm_bytes(
                self.model,
                plan=snap.get("plan", {}).get("plan_bytes"),
                live=snap.get("live_bytes"),
                peak=snap.get("peak_bytes"))
        except Exception:
            pass

    # -- panes ---------------------------------------------------------
    def snapshot(self) -> dict[str, Any]:
        return {
            "compile": self.ledger.snapshot(),
            "transfers": self.transfers.snapshot(),
            "hbm": self.hbm_snapshot(),
        }

    def fleet_summary(self) -> dict[str, Any]:
        """Compact dict for the heartbeat blob / brief status — bounded
        size (the slab blob is shared with probe + SLO payloads)."""
        hbm = self.hbm_snapshot()
        return {
            "compiles": self.ledger.compiles,
            "recompiles": self.ledger.recompile_count(),
            "h2d_chain": self.transfers.count("h2d", "chain"),
            "hbm_measured": bool(hbm.get("measured")),
            "hbm_live_bytes": hbm.get("live_bytes", 0),
        }
