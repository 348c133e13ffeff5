"""The one general traffic generator: a mix's data file and a seed in, requests out.

A pure function of (mix, rate or clients, seconds, seed); no clock, no I/O. The
program under test receives only what this emits.

The *schedule* of a mix (when each request is due, how long its prompt is and
how many tokens it asks for) is one sample path, fixed by the mix's
``schedule_seed``: independent exponential gaps (a Poisson process, its bursts
and lulls left as they fall) and independent draws from the length
distributions. ``--seed`` decides what the requests say: the prompt text and
each request's sampling seed. So every run of a cell does the same work at the
same moments, whatever its seed, and what differs between two runs is the
machine. Nothing is reordered or balanced to steady a metric.
"""

from __future__ import annotations

import math
import random
from dataclasses import dataclass

PRINTABLE = "".join(chr(c) for c in range(32, 127))


@dataclass(frozen=True)
class Req:
    index: int
    due_s: float          # open loop: seconds after the window opens; closed loop: 0
    messages: tuple       # the chat messages as sent
    max_tokens: int
    seed: int


def draw(dist: dict, rng: random.Random) -> int:
    """One length from the distribution, rounded and clipped to its limits."""
    kind = dist["dist"]
    if kind == "lognormal":
        x = float(dist["median"]) * math.exp(float(dist["sigma"]) * rng.gauss(0.0, 1.0))
    elif kind == "uniform":
        x = rng.uniform(float(dist["min"]), float(dist["max"]))
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    return min(max(int(round(x)), int(dist.get("min", 1))), int(dist.get("max", 1 << 30)))


def text(rng: random.Random, n: int) -> str:
    return "".join(rng.choices(PRINTABLE, k=n))


def _schedule_rng(mix: dict, stream: str) -> random.Random:
    return random.Random(f"{mix['schedule_seed']}/{stream}")


def _request(mix: dict, index: int, due_s: float, sched: random.Random, rng: random.Random) -> Req:
    p = draw(mix["prompt_tokens"], sched)
    o = draw(mix["output_tokens"], sched)
    o = max(min(o, int(mix.get("max_total_tokens", 1 << 30)) - p), 1)
    return Req(index=index, due_s=due_s, messages=({"role": "user", "content": text(rng, p)},),
               max_tokens=o, seed=rng.randrange(1, 2**31 - 1))


def open_loop(mix: dict, rate_rps: float, seconds: float, seed: int, stream: str = "window") -> list[Req]:
    """The requests due in [0, seconds): a Poisson process of the given rate.
    The same ``stream`` of the same mix is the same sample path at every rate
    (gaps scale with 1 / rate) and a shorter window is a prefix of a longer one."""
    process = (mix.get("arrivals") or {}).get("process", "poisson")
    if process != "poisson":
        raise ValueError(f"unknown arrival process {process!r}")
    sched, rng = _schedule_rng(mix, stream), random.Random(seed)
    out: list[Req] = []
    t = sched.expovariate(1.0) / rate_rps
    while t < seconds:
        out.append(_request(mix, len(out), t, sched, rng))
        t += sched.expovariate(1.0) / rate_rps
    return out


def closed_loop(mix: dict, n: int, seed: int) -> list[Req]:
    """The shared queue of a closed loop: ``n`` requests in a fixed order of
    lengths. A client takes the next request when its last one ends; the window
    ends the run, not the queue."""
    sched, rng = _schedule_rng(mix, "queue"), random.Random(seed)
    return [_request(mix, i, 0.0, sched, rng) for i in range(n)]


def body(req: Req, model: str, sampling: dict, bias_ids: list[int] | None,
         bias: int = 100) -> dict:
    """The JSON body of one streamed chat completion."""
    doc = {"model": model, "messages": list(req.messages), "max_tokens": req.max_tokens,
           "temperature": sampling.get("temperature", 0.7), "top_p": sampling.get("top_p", 1.0),
           "seed": req.seed, "stream": True, "stream_options": {"include_usage": True}}
    if bias_ids:
        doc["logit_bias"] = {str(i): bias for i in bias_ids}
    return doc
