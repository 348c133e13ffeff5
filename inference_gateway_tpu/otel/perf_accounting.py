"""Compute-efficiency accounting (ISSUE 6 tentpole).

The profiling stack (ISSUE 4) says how long each engine step took; this
module says how long it *should* have taken, from nothing but the model
config and the chip's datasheet. Three cooperating pieces:

- ``StepCostModel`` — the analytic cost of one engine step per kind
  (``prefill`` / ``decode`` / ``spec`` / ``spec_ngram`` / ``mixed`` —
  the ISSUE 12 ragged step, priced per-row from the same descriptors
  the kernel consumes): FLOPs from the
  2·N-params-per-token rule plus the attention terms, HBM traffic from
  the resident weight stream plus KV read/write, and the roofline time
  ``max(flops/peak, bytes/bw)`` with a compute- vs bandwidth-bound
  verdict. Built from the same byte-accounting primitives as
  ``serving/profiles.hbm_plan`` so the two can't silently diverge (a
  drift test pins both against what the Engine actually allocates).
- ``PerfAccounting`` — the always-on runtime tracker attached to a
  Scheduler: every recorded engine step lands in a rolling window from
  which live MFU, HBM-bandwidth utilization, and per-kind
  gap-to-roofline ratios are derived and pushed into the Registry
  gauges (``engine.mfu``, ``engine.hbm_bandwidth_util``,
  ``engine.step_roofline_ratio{kind}``). Wasted work — speculation
  rejections, chunk-overrun tokens, tokens decoded for disconnected
  clients, shed-after-prefill — is attributed by reason
  (``engine.wasted_tokens{reason}``), and *goodput*-MFU (useful tokens
  only) is reported alongside raw MFU.
- ``roofline_report`` — the ``GET /debug/roofline`` aggregation:
  per-step-kind measured-vs-analytic percentiles, achieved TFLOP/s and
  GB/s, and gap factor over the timeline ring. Off-TPU the wall-clock
  side is host time, not device time, so the report is explicitly
  framed ``measured: false`` and never emits an ``mfu_measured`` key —
  analytic numbers move every round, measured numbers only on a chip.

Everything is zero-overhead when off: the scheduler hot path pays one
``is None`` check per engine *chunk*, and with accounting disabled no
window, no gauges, and no counters exist.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Any, Callable

# ---------------------------------------------------------------------------
# Chip datasheet table (bf16 peak, HBM bandwidth). v5e anchors the
# committed profiles (serving/profiles.py); the others cover the common
# fleet so a profile ported to a new slice keeps an honest roofline.
# A live engine finds its row by the ``device_kind`` JAX reports
# (CHIP_BY_DEVICE_KIND, the strings of jax's own pallas tpu_info); a
# kind that is not listed has no roofline — never another chip's.
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ChipSpec:
    name: str
    peak_flops: float  # bf16 FLOP/s per chip
    hbm_bw: float  # bytes/s per chip


CHIP_SPECS: dict[str, ChipSpec] = {
    "v5e": ChipSpec("v5e", 197e12, 819e9),
    "v5p": ChipSpec("v5p", 459e12, 2765e9),
    "v4": ChipSpec("v4", 275e12, 1228e9),
    "v6e": ChipSpec("v6e", 918e12, 1640e9),
}

CHIP_BY_DEVICE_KIND: dict[str, str] = {
    "TPU v4": "v4",
    "TPU v5 lite": "v5e",
    "TPU v5e": "v5e",
    "TPU v5": "v5p",
    "TPU v5p": "v5p",
    "TPU v6 lite": "v6e",
    "TPU v6e": "v6e",
}


class UnknownChipError(ValueError):
    """No datasheet row for this device: there is no roofline to price
    against, and another chip's figures are never substituted."""

# Wasted-work attribution reasons (engine.wasted_tokens{reason}).
WASTE_SPEC_REJECTED = "spec_rejected"  # verify-forward positions the target refused
WASTE_CHUNK_OVERRUN = "chunk_overrun"  # decoded past a finish inside a fused chunk
WASTE_DISCONNECTED = "disconnected"  # decoded for a client that already hung up
WASTE_SHED_AFTER_PREFILL = "shed_after_prefill"  # prefilled, then failed/shed
# One decode row of the mixed step dispatched behind the step whose token,
# seen a step later, ended the stream (EOS, a stop id, a client gone; ISSUE 40).
WASTE_OVERSHOOT = "overshoot"


def detect_tpu() -> bool:
    """True only when step wall-times are device times (a live TPU
    backend). Anything else — CPU, interpret mode, no jax — means the
    measured side of the roofline is host clock, not hardware."""
    try:
        from inference_gateway_tpu.utils.platform import on_tpu

        return on_tpu()
    except Exception:
        return False


# ---------------------------------------------------------------------------
# Analytic per-step cost
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class StepCost:
    flops: float
    hbm_bytes: float
    roofline_s: float
    bound: str  # "compute" | "bandwidth"


class StepCostModel:
    """FLOP/byte/roofline cost of engine steps, from the model config.

    All quantities are aggregates over the whole mesh (``n_chips`` chips
    of ``chip``): FLOPs and bytes are totals, peak/bandwidth are
    ``n_chips ×`` the datasheet — so ``mfu = flops / (t · peak_total)``
    is directly the fleet-average MFU the BENCH trajectory reports.

    Validated against the closed-form 2·N-params-per-token rule:
    ``decode(batch=B, n_steps=1, context_tokens=0).flops == 2·N·B``
    (tests/test_perf_accounting.py).
    """

    def __init__(self, model_cfg: Any, *, chip: ChipSpec, n_chips: int = 1,
                 quantize: str | None = None, spec_k: int = 0,
                 draft_cfg: Any = None) -> None:
        from inference_gateway_tpu.models import mixtral
        from inference_gateway_tpu.serving.profiles import (
            kv_bytes_per_token,
            llama_param_count,
            mixtral_param_count,
        )

        self.model_cfg = model_cfg
        self.n_chips = max(int(n_chips), 1)
        self.chip = chip
        self.spec_k = int(spec_k)
        cfg = model_cfg
        is_moe = isinstance(cfg, mixtral.MixtralConfig)

        wbytes = {"int8": 1.0, "int4": 0.5}.get(quantize, 2.0)
        embed_params = cfg.vocab_size * cfg.hidden_size
        if is_moe:
            n_params = mixtral_param_count(cfg)
            expert_params = (cfg.num_layers * cfg.num_experts
                             * 3 * cfg.hidden_size * cfg.intermediate_size)
            dense_params = n_params - expert_params
            # Per token only experts_per_token experts run; the rest of
            # the tree is dense. (Capacity-factor padding is real extra
            # work but implementation-dependent; the analytic floor
            # prices the routed tokens only.)
            active_expert_params = (expert_params * cfg.experts_per_token
                                    // cfg.num_experts)
            self.active_params = dense_params + active_expert_params
            self._expert_params = expert_params
            self._dense_weight_bytes = (embed_params * 2
                                        + (dense_params - embed_params) * wbytes)
            self._expert_weight_bytes = expert_params * wbytes
        else:
            n_params = llama_param_count(cfg)
            self.active_params = n_params
            self._expert_params = 0
            self._dense_weight_bytes = (embed_params * 2
                                        + (n_params - embed_params) * wbytes)
            self._expert_weight_bytes = 0.0
        self.n_params = n_params
        self.is_moe = is_moe
        self.experts_per_token = getattr(cfg, "experts_per_token", 0)
        self.num_experts = getattr(cfg, "num_experts", 0)
        self.weight_bytes = self._dense_weight_bytes + self._expert_weight_bytes
        self.kv_bytes_per_token = kv_bytes_per_token(cfg)
        # Attention score+value FLOPs per (query token, context token)
        # pair: QKᵀ and A·V are 2 FLOPs each per element over Hq·D.
        self.attn_flops_per_pair = 4 * cfg.num_layers * cfg.num_heads * cfg.hd
        # Model-draft speculation: the draft's own forward rides every
        # round (ngram drafting is host-side and free).
        self.draft_params = 0
        self.draft_weight_bytes = 0.0
        if draft_cfg is not None:
            self.draft_params = llama_param_count(draft_cfg)
            self.draft_weight_bytes = self.draft_params * 2.0

    # -- totals over the mesh ------------------------------------------
    @property
    def peak_flops_total(self) -> float:
        return self.chip.peak_flops * self.n_chips

    @property
    def hbm_bw_total(self) -> float:
        return self.chip.hbm_bw * self.n_chips

    def flops_per_token(self, context_len: int = 0) -> float:
        """Decode FLOPs for ONE token at a given context length — the
        unit goodput-MFU bills useful tokens at."""
        return 2.0 * self.active_params + self.attn_flops_per_pair * context_len

    def _expert_stream_bytes(self, tokens: int) -> float:
        """HBM bytes of expert weights streamed for `tokens` routed
        tokens: with few tokens only the touched experts page in; a big
        batch touches (almost) all of them."""
        if not self.is_moe:
            return 0.0
        frac = min(1.0, tokens * self.experts_per_token / max(self.num_experts, 1))
        return self._expert_weight_bytes * frac

    def _cost(self, flops: float, hbm_bytes: float) -> StepCost:
        t_compute = flops / self.peak_flops_total
        t_bw = hbm_bytes / self.hbm_bw_total
        return StepCost(
            flops=flops, hbm_bytes=hbm_bytes,
            roofline_s=max(t_compute, t_bw),
            bound="compute" if t_compute >= t_bw else "bandwidth",
        )

    # -- step kinds ----------------------------------------------------
    def decode(self, batch: int, n_steps: int = 1, context_tokens: int = 0) -> StepCost:
        """A fused decode chunk: ``n_steps`` engine steps over ``batch``
        live slots whose current sequence lengths sum to
        ``context_tokens``. Each step streams the resident weights once
        and reads every live sequence's KV."""
        tokens = batch * n_steps
        flops = (tokens * 2.0 * self.active_params
                 + n_steps * self.attn_flops_per_pair * context_tokens)
        step_bytes = (self._dense_weight_bytes
                      + self._expert_stream_bytes(batch)
                      + context_tokens * self.kv_bytes_per_token  # KV read
                      + batch * self.kv_bytes_per_token)  # KV write
        return self._cost(flops, n_steps * step_bytes)

    def prefill(self, tokens: int, sq_tokens: int = 0) -> StepCost:
        """A batched prefill of ``tokens`` total prompt tokens;
        ``sq_tokens`` is Σ Tᵢ² over the batch (the causal-attention
        quadratic term prices T²/2 query·key pairs per sequence)."""
        flops = (tokens * 2.0 * self.active_params
                 + self.attn_flops_per_pair * sq_tokens / 2.0)
        hbm_bytes = (self._dense_weight_bytes
                     + self._expert_stream_bytes(tokens)
                     + 2.0 * tokens * self.kv_bytes_per_token)  # KV write + re-read
        return self._cost(flops, hbm_bytes)

    def spec(self, batch: int, context_tokens: int = 0, *, ngram: bool = True) -> StepCost:
        """One speculative round: the target verifies K draft proposals
        plus the pending token — K+1 positions per slot — in a single
        forward (one weight stream prices them all: the whole point of
        speculation). Model-draft rounds additionally pay the draft's
        K-token autoregressive forward; ngram drafting is host-side."""
        k1 = self.spec_k + 1
        positions = batch * k1
        flops = (positions * 2.0 * self.active_params
                 + self.attn_flops_per_pair * context_tokens * k1)
        hbm_bytes = (self._dense_weight_bytes
                     + self._expert_stream_bytes(positions)
                     + context_tokens * self.kv_bytes_per_token * k1
                     + positions * self.kv_bytes_per_token)
        if not ngram and self.draft_params:
            flops += batch * self.spec_k * 2.0 * self.draft_params
            hbm_bytes += self.spec_k * self.draft_weight_bytes
        return self._cost(flops, hbm_bytes)

    def mixed(self, *, work_tokens: int, context_tokens: int = 0,
              pair_tokens: int = 0) -> StepCost:
        """One ragged MIXED step (ISSUE 12): ``work_tokens`` query
        positions — decode rows plus prefill-chunk tokens — share one
        weight stream; attention is priced from the exact per-row
        descriptors the scheduler assembled: ``pair_tokens`` = Σ over
        queries of their attended span (the FLOPs term), and
        ``context_tokens`` = Σ over rows of their kv length (the KV read
        stream). With only decode rows this reduces exactly to
        ``decode(batch, 1, context)``; a lone fresh prefill row reduces
        to ``prefill(T, T²)`` — pinned by tests."""
        tokens = max(work_tokens, 1)
        flops = (tokens * 2.0 * self.active_params
                 + self.attn_flops_per_pair * pair_tokens)
        hbm_bytes = (self._dense_weight_bytes
                     + self._expert_stream_bytes(tokens)
                     + context_tokens * self.kv_bytes_per_token  # KV read
                     + tokens * self.kv_bytes_per_token)  # KV write
        return self._cost(flops, hbm_bytes)

    def step_cost(self, kind: str, *, batch: int, n_steps: int = 1, tokens: int = 0,
                  context_tokens: int = 0, sq_tokens: int = 0,
                  pair_tokens: int = 0) -> StepCost:
        if kind == "prefill":
            return self.prefill(tokens=max(tokens, batch), sq_tokens=sq_tokens)
        if kind == "spec":
            return self.spec(batch, context_tokens, ngram=False)
        if kind == "spec_ngram":
            return self.spec(batch, context_tokens, ngram=True)
        if kind == "mixed":
            return self.mixed(work_tokens=max(tokens, batch),
                              context_tokens=context_tokens, pair_tokens=pair_tokens)
        return self.decode(batch, n_steps=max(n_steps, 1), context_tokens=context_tokens)

    # -- constructors --------------------------------------------------
    @classmethod
    def from_engine(cls, engine: Any, chip: str | None = None) -> "StepCostModel":
        """Build from a live Engine: model config, quantization, mesh
        size, and (for model-draft spec) the draft config all come from
        what the engine actually runs. ``chip`` names a datasheet row
        explicitly (TELEMETRY_ACCOUNTING_CHIP); otherwise the row comes
        from the device's ``device_kind``, and an unlisted kind raises
        UnknownChipError — unknown device, no roofline."""
        if chip is None:
            from inference_gateway_tpu.utils.platform import device_info

            kind = device_info()["device_kind"]
            chip = CHIP_BY_DEVICE_KIND.get(kind)
            if chip is None:
                raise UnknownChipError(
                    f"unknown device kind {kind!r}: no roofline (name a chip of "
                    f"{sorted(CHIP_SPECS)} in TELEMETRY_ACCOUNTING_CHIP to price against it)")
        if chip not in CHIP_SPECS:
            raise UnknownChipError(f"unknown chip {chip!r}: expected one of {sorted(CHIP_SPECS)}")
        spec = CHIP_SPECS[chip]
        n_chips = engine.mesh.devices.size if engine.mesh is not None else 1
        return cls(
            engine.model_cfg,
            n_chips=n_chips,
            chip=spec,
            quantize=engine.config.quantize,
            spec_k=engine.config.spec_k if engine.spec else 0,
            draft_cfg=getattr(engine, "draft_cfg", None)
            if (engine.spec and not engine.spec_ngram) else None,
        )

    @classmethod
    def from_profile(cls, profile: Any) -> "StepCostModel":
        """Build from a committed ServingProfile (no engine, no arrays).
        Every committed profile is a v5e deployment (profiles.py sizes
        them against V5E_HBM_BYTES), hence the named row."""
        from inference_gateway_tpu.serving.profiles import resolve_model_cfg

        return cls(
            resolve_model_cfg(profile.model),
            n_chips=profile.n_chips,
            chip=CHIP_SPECS["v5e"],
            quantize=profile.quantize,
        )


# ---------------------------------------------------------------------------
# Rolling-window runtime accounting
# ---------------------------------------------------------------------------


class PerfAccounting:
    """Live compute-efficiency tracker fed by the scheduler's step
    records. Thread discipline matches StepTimeline: the scheduler
    thread writes under a lock, readers snapshot under the same lock.

    ``measured`` is pinned at construction: only a live TPU backend may
    ever frame wall-clock-derived numbers as hardware measurements."""

    # Gauges are scrape-read: refresh them at most this often, not per
    # engine chunk (the accounting-overhead bench gates at <5% p99).
    GAUGE_INTERVAL_S = 0.5

    def __init__(self, cost_model: StepCostModel, *, otel: Any = None, model: str = "",
                 window_s: float = 10.0, measured: bool | None = None,
                 now_fn: Callable[[], float] | None = None) -> None:
        self.cost = cost_model
        self.otel = otel
        self.model = model
        # Injectable time source (graftlint clock-discipline): window
        # pruning and gauge pacing read through it, so tests can age the
        # window without real waiting.
        self._now = now_fn or time.monotonic
        self.window_s = max(float(window_s), 0.5)
        self.measured = detect_tpu() if measured is None else bool(measured)
        self._lock = threading.Lock()
        # (t, kind, duration_s, flops, hbm_bytes, roofline_s, tokens)
        self._events: deque[tuple] = deque()
        # (t, tokens) DELIVERED-then-wasted inside the window, for
        # goodput-MFU: only waste that was first counted as a delivered
        # token (disconnected streams, shed streams' emitted tokens) may
        # be subtracted from the delivered total — spec rejections and
        # chunk overrun were never delivered, so their cost already
        # shows up as the raw-vs-goodput gap without subtraction.
        self._wasted_events: deque[tuple] = deque()
        self.wasted: dict[str, int] = {}
        # Window aggregates, maintained incrementally on append/prune so
        # the per-step cost is O(1), never O(events-in-window).
        self._w_flops = 0.0
        self._w_bytes = 0.0
        self._w_tokens = 0
        self._w_dur = 0.0
        self._w_wasted = 0
        self._w_kind: dict[str, list] = {}  # kind -> [measured_s, analytic_s, n]
        self._gauges_at = 0.0
        # Lifetime totals (survive window pruning; /metrics counters).
        self.total_flops = 0.0
        self.total_bytes = 0.0
        self.total_tokens = 0
        self.total_steps = 0

    # -- feeders (scheduler thread) ------------------------------------
    def on_step(self, kind: str, duration_s: float, *, batch: int, n_steps: int = 1,
                tokens: int = 0, work_tokens: int = 0, context_tokens: int = 0,
                sq_tokens: int = 0, pair_tokens: int = 0) -> dict[str, Any]:
        """Price one recorded engine step; returns the cost fields the
        StepTimeline merges into its record. ``tokens`` is what reached
        clients (the goodput numerator); ``work_tokens`` what the step
        actually processed (prefill prices prompt tokens, not the batch
        of first tokens it emits; mixed steps price every packed query
        position)."""
        cost = self.cost.step_cost(kind, batch=batch, n_steps=n_steps,
                                   tokens=work_tokens or tokens,
                                   context_tokens=context_tokens, sq_tokens=sq_tokens,
                                   pair_tokens=pair_tokens)
        now = self._now()
        win = None
        with self._lock:
            self._events.append((now, kind, duration_s, cost.flops, cost.hbm_bytes,
                                 cost.roofline_s, tokens))
            self._w_flops += cost.flops
            self._w_bytes += cost.hbm_bytes
            self._w_tokens += tokens
            self._w_dur += duration_s
            agg = self._w_kind.setdefault(kind, [0.0, 0.0, 0])
            agg[0] += duration_s
            agg[1] += cost.roofline_s
            agg[2] += 1
            self.total_flops += cost.flops
            self.total_bytes += cost.hbm_bytes
            self.total_tokens += tokens
            self.total_steps += n_steps
            self._prune(now)
            if self.otel is not None and now - self._gauges_at >= self.GAUGE_INTERVAL_S:
                self._gauges_at = now
                win = self._window_locked(now)
        if win is not None:
            self.otel.set_compute_efficiency(
                self.model, mfu=win["mfu"],
                hbm_bandwidth_util=win["hbm_bandwidth_util"],
                goodput_mfu=win["goodput_mfu"])
            for k, ratio in win["roofline_ratio"].items():
                self.otel.set_step_roofline_ratio(self.model, k, ratio)
        return {
            "flops": cost.flops,
            "hbm_bytes": cost.hbm_bytes,
            "roofline_ms": round(cost.roofline_s * 1e3, 4),
            "bound": cost.bound,
        }

    def record_wasted(self, reason: str, tokens: int = 1, *,
                      delivered: int = 0) -> None:
        """Attribute wasted work: tokens the engine computed that no
        client will ever see (the accounting substrate per-tenant quotas
        bill against). ``delivered`` is the subset of ``tokens`` that was
        previously counted in the delivered-token window (a token emitted
        to a stream nobody reads) — only those are subtracted from the
        goodput numerator; never-delivered waste (rejected speculation,
        chunk overrun) is already absent from it."""
        if tokens <= 0:
            return
        delivered = min(max(delivered, 0), tokens)
        now = self._now()
        with self._lock:
            self.wasted[reason] = self.wasted.get(reason, 0) + tokens
            if delivered:
                self._wasted_events.append((now, delivered))
                self._w_wasted += delivered
        if self.otel is not None:
            self.otel.record_wasted_tokens(self.model, reason, tokens)

    # -- derived state -------------------------------------------------
    def _prune(self, now: float) -> None:
        horizon = now - self.window_s
        ev = self._events
        while ev and ev[0][0] < horizon:
            _t, kind, dur, flops, hbm, roofline, tokens = ev.popleft()
            self._w_flops -= flops
            self._w_bytes -= hbm
            self._w_tokens -= tokens
            self._w_dur -= dur
            agg = self._w_kind.get(kind)
            if agg is not None:
                agg[0] -= dur
                agg[1] -= roofline
                agg[2] -= 1
                if agg[2] <= 0:
                    del self._w_kind[kind]
        wev = self._wasted_events
        while wev and wev[0][0] < horizon:
            self._w_wasted -= wev.popleft()[1]

    def _window_locked(self, now: float) -> dict[str, Any]:
        ev = self._events
        if not ev:
            return {"mfu": 0.0, "hbm_bandwidth_util": 0.0, "goodput_mfu": 0.0,
                    "roofline_ratio": {}, "tokens_per_sec": 0.0, "steps": 0}
        span = max(now - ev[0][0], self._w_dur, 1e-6)
        wasted = max(self._w_wasted, 0)
        useful = max(self._w_tokens - wasted, 0)
        mfu = self._w_flops / (span * self.cost.peak_flops_total)
        # Goodput bills useful tokens at the ideal per-token cost — the
        # MFU the fleet would show if no work had been thrown away.
        goodput = (useful * self.cost.flops_per_token()) / (span * self.cost.peak_flops_total)
        ratios = {kind: agg[0] / agg[1]
                  for kind, agg in self._w_kind.items() if agg[1] > 0}
        return {
            "mfu": mfu,
            "hbm_bandwidth_util": self._w_bytes / (span * self.cost.hbm_bw_total),
            "goodput_mfu": min(goodput, mfu),
            "roofline_ratio": ratios,
            "tokens_per_sec": self._w_tokens / span,
            "steps": len(ev),
        }

    def snapshot(self) -> dict[str, Any]:
        """The mfu snapshot /debug/status, /metrics, and the OTLP push
        carry. Keys are framing-safe: window numbers derive from wall
        clock and are labeled ``measured`` only on a TPU backend."""
        now = self._now()
        with self._lock:
            self._prune(now)
            win = self._window_locked(now)
            wasted = dict(self.wasted)
            totals = {
                "flops": self.total_flops,
                "hbm_bytes": self.total_bytes,
                "tokens": self.total_tokens,
                "steps": self.total_steps,
            }
        return {
            "measured": self.measured,
            "chip": self.cost.chip.name,
            "n_chips": self.cost.n_chips,
            "window_seconds": self.window_s,
            "mfu": round(win["mfu"], 6),
            "goodput_mfu": round(win["goodput_mfu"], 6),
            "hbm_bandwidth_util": round(win["hbm_bandwidth_util"], 6),
            "roofline_ratio": {k: round(v, 3) for k, v in win["roofline_ratio"].items()},
            "tokens_per_sec": round(win["tokens_per_sec"], 1),
            "wasted_tokens": wasted,
            "totals": totals,
        }

    def request_flops(self, prompt_tokens: int, output_tokens: int) -> tuple[float, float]:
        """Per-request attribution for the access log: (prefill_flops,
        decode_flops) of one request's useful work — prompt ingestion
        plus each output token priced at its growing context length."""
        prefill = self.cost.prefill(prompt_tokens, sq_tokens=prompt_tokens ** 2).flops
        # Σ over output tokens of flops_per_token(prompt + i) — closed
        # form via the arithmetic series.
        n = max(output_tokens, 0)
        avg_ctx = prompt_tokens + n / 2.0
        decode = n * (2.0 * self.cost.active_params
                      + self.cost.attn_flops_per_pair * avg_ctx)
        return prefill, decode


# ---------------------------------------------------------------------------
# /debug/roofline aggregation
# ---------------------------------------------------------------------------


def _pick(xs: list[float], q: float) -> float:
    return xs[min(len(xs) - 1, int(len(xs) * q))] if xs else 0.0


def roofline_report(accounting: PerfAccounting,
                    entries: list[dict[str, Any]]) -> dict[str, Any]:
    """Aggregate measured-vs-analytic per step kind over the timeline
    ring — the one endpoint a kernel PR points at before/after.

    ``gap_factor`` is measured-p50 / analytic-p50: ≥ 1 on hardware means
    "this far from the roofline"; off-TPU the same number is a *host*
    gap (Python + dispatch, not kernel time) and the report
    says so — the entries keep the analytic keys either way so the
    trajectory moves every round."""
    per_kind: dict[str, dict[str, Any]] = {}
    by_kind: dict[str, list[dict[str, Any]]] = {}
    for rec in entries:
        if "flops" in rec:
            by_kind.setdefault(rec["kind"], []).append(rec)
    for kind, recs in by_kind.items():
        durs = sorted(r["duration_ms"] for r in recs)
        roofs = sorted(r["roofline_ms"] for r in recs)
        sum_dur_s = sum(durs) / 1e3
        sum_flops = sum(r["flops"] for r in recs)
        sum_bytes = sum(r["hbm_bytes"] for r in recs)
        p50_d, p99_d = _pick(durs, 0.50), _pick(durs, 0.99)
        p50_r = _pick(roofs, 0.50)
        bounds = [r.get("bound", "bandwidth") for r in recs]
        per_kind[kind] = {
            "records": len(recs),
            "tokens": sum(r["tokens"] for r in recs),
            "step_ms_p50": round(p50_d, 3),
            "step_ms_p99": round(p99_d, 3),
            "analytic_ms_p50": round(p50_r, 4),
            "achieved_tflops": round(sum_flops / max(sum_dur_s, 1e-9) / 1e12, 4),
            "achieved_gbps": round(sum_bytes / max(sum_dur_s, 1e-9) / 1e9, 3),
            "gap_factor": round(p50_d / p50_r, 2) if p50_r > 0 else None,
            "bound": max(set(bounds), key=bounds.count),
        }
        # Host gap between chained chunks (ISSUE 14): the host's wall
        # time between fetching chunk N and dispatching chunk N+1 —
        # p50/p99 per step kind, present only where the scheduler
        # stamped it (chained decode dispatches).
        gaps = sorted(r["host_gap_ms"] for r in recs if "host_gap_ms" in r)
        if gaps:
            per_kind[kind]["host_gap_ms_p50"] = round(_pick(gaps, 0.50), 4)
            per_kind[kind]["host_gap_ms_p99"] = round(_pick(gaps, 0.99), 4)
    out: dict[str, Any] = {
        "measured": accounting.measured,
        "chip": accounting.cost.chip.name,
        "n_chips": accounting.cost.n_chips,
        "peak_tflops_total": round(accounting.cost.peak_flops_total / 1e12, 1),
        "hbm_gbps_total": round(accounting.cost.hbm_bw_total / 1e9, 1),
        "window": accounting.snapshot(),
        "per_kind": per_kind,
    }
    if accounting.measured:
        out["mfu_measured"] = out["window"]["mfu"]
    else:
        out["note"] = ("step times are HOST wall clock (no TPU backend): "
                       "gap factors include Python/dispatch overhead and must "
                       "not be read as kernel efficiency")
    return out
