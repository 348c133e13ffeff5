"""Arithmetic shared by the metric readers, and the shape a right answer has."""

from __future__ import annotations

import math

P95_MIN_REQUESTS = 200  # so that ten samples lie beyond the 95th percentile


def percentile(values: list[float], q: float) -> float | None:
    """The q-th percentile (0..100) by linear interpolation between ranks."""
    if not values:
        return None
    xs = sorted(values)
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def median(values: list[float]) -> float | None:
    return percentile(values, 50.0)


def p95(values: list[float], min_n: int = P95_MIN_REQUESTS) -> float | None:
    """The 95th percentile, or nothing when fewer than ``min_n`` samples back it."""
    return percentile(values, 95.0) if len(values) >= min_n else None


def ttft_ms(r) -> float | None:
    """First *content* frame minus the moment the request was due."""
    if r.first_content_t is None:
        return None
    return (r.first_content_t - r.due_t) * 1e3


def tpot_ms(r) -> float | None:
    """(last content frame - first) / (tokens - 1); under the bias one
    character is one token."""
    if r.first_content_t is None or r.content_chars < 2:
        return None
    return (r.last_content_t - r.first_content_t) / (r.content_chars - 1) * 1e3


def measured(sources: dict) -> list:
    """The requests the window's metrics are taken over: due in the window,
    and answered."""
    return [r for r in sources["requests"] if r.error is None]


def expected_prompt_tokens(messages, template: dict) -> int:
    """Prompt tokens the byte tokenizer makes of these messages: one per
    character of the templated text, plus BOS."""
    n = int(template.get("bos_tokens", 1)) + len(template["generation_prompt"])
    for m in messages:
        n += len(template["message"].replace("{role}", m["role"]).replace("{content}", m["content"]))
    return n


def stream_fault(r, want_prompt_tokens: int | None) -> str | None:
    """Why a finished stream is not a right answer, or None. One completion id,
    the role preamble, one finish_reason, usage that adds up and equals the
    tokens seen in content frames, every token asked for, [DONE] last."""
    if r.error is not None:
        return None  # counted in ``failed``, not a malformed answer
    if r.malformed:
        return r.malformed
    if len(r.ids) != 1 or not str(next(iter(r.ids))).startswith("chatcmpl-"):
        return f"completion ids {sorted(map(str, r.ids))}"
    if r.role_frames < 1:
        return "no role preamble"
    if len(r.finishes) != 1 or r.finishes[0] not in ("stop", "length"):
        return f"finish_reasons {r.finishes}"
    if not r.done_last:
        return "[DONE] is not the last event"
    if len(r.usages) != 1:
        return f"{len(r.usages)} usage chunks"
    u = r.usages[0]
    done, asked = u.get("completion_tokens"), u.get("prompt_tokens")
    if not isinstance(done, int) or not isinstance(asked, int):
        return f"usage not integers: {u}"
    if u.get("total_tokens") != asked + done:
        return f"total_tokens does not add up: {u}"
    if done != r.content_chars:
        return f"usage says {done} tokens, content frames carried {r.content_chars}"
    if done != r.max_tokens:
        return f"{done} tokens of {r.max_tokens} asked for (a token was lost)"
    if want_prompt_tokens is not None and asked != want_prompt_tokens:
        return f"prompt_tokens {asked}, sent {want_prompt_tokens}"
    return None


def mean_live_context(requests, t0: float, t1: float) -> tuple[float, float]:
    """(mean tokens of context held by decoding requests, mean decoding
    requests) over the monotonic interval [t0, t1], from the client's own
    records: a request decodes from its first content frame to its last, and its
    context grows from its prompt by one token a frame's token."""
    if t1 <= t0:
        return 0.0, 0.0
    tokens = rows = 0.0
    for r in requests:
        if r.first_content_t is None or len(r.usages) != 1:
            continue
        first, last = r.first_content_t, max(r.last_content_t, r.first_content_t + 1e-9)
        u, v = max(first, t0), min(last, t1)
        if v <= u:
            continue
        grown = r.content_chars * ((u + v) / 2 - first) / (last - first)
        tokens += (v - u) * (r.usages[0]["prompt_tokens"] + grown)
        rows += v - u
    return tokens / (t1 - t0), rows / (t1 - t0)
