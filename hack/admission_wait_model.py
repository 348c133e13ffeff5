#!/usr/bin/env python3
"""A queue model of what an open-loop request waits for in front of its first token (ISSUE 48).

    python3 hack/admission_wait_model.py --chunk-ms 89.3 --mixed-ms 50.2 --prompt-steps 1 --rate 4.2 \\
        --depth 1 --chunk-steps 8
    python3 hack/admission_wait_model.py --table      # the three open-loop cells, depth 2 / 1, 8 / 4 / 2 steps a chunk

It walks ``Scheduler._pass`` as the code reads, on one device that runs what
it is handed in order, with decode rows always present (the chat cells hold
10-13) and a slot always free:

- a pass with nobody waiting submits one decode chunk (``--host-ms`` of host
  work), and fetches the oldest while more than ``--depth`` are unfetched: the
  thread sits in that fetch until the chunk ends, and sees no arrival before;
- a pass with somebody waiting stamps ``admit``, drains every chunk still
  queued (none can be cancelled), then runs the train: ``--prompt-steps``
  mixed steps a request, back to back (the train runs one step ahead of the
  host), a request's first token leaving when its last step ends; whoever
  arrived meanwhile is admitted by the next pass, which finds nothing queued;
- behind a train the fresh chunk is dispatched before the train's last step
  is fetched.

``--chunk-ms`` is the device time of an EIGHT-step chunk (8 x the cell's
``decode_step_ms``); ``--chunk-steps`` n makes a chunk n / 8 of it. Nothing is
fitted: the four durations are a cell's own readings. The model knows no
gateway, no HTTP, no slot limit and no variation of a step's time, and a
prompt's steps are whole; it is for sizing a change to the queue, not for
predicting a level. Stays off JAX; seconds of CPU.
"""
import argparse
import json
import random
import statistics
from collections import deque

# decode_step_ms x 8, mixed_step_device_ms, mixed steps a prompt, offered req/s (ledger, PR 47, change side).
CELLS = {
    "mistral-7b.chat": (89.3, 50.2, 1, 4.2),
    "qwen2.5-7b.chat": (90.2, 48.2, 1, 5.3),
    "mistral-7b.long-prompt": (85.2, 53.2, 3, 1.7),
}


def simulate(chunk_ms: float, mixed_ms: float, prompt_steps: int, rate: float, depth: int,
             chunk_steps: int = 8, host_ms: float = 1.0, seconds: float = 600.0, seed: int = 0) -> dict:
    rng = random.Random(seed)
    arrivals, t = [], 0.0
    while t < seconds * 1e3:
        t += rng.expovariate(rate / 1e3)
        arrivals.append(t)
    chunk = chunk_ms * chunk_steps / 8
    now = device_free = 0.0
    handles: deque = deque()          # end times of the chunks dispatched and not fetched
    train_end = None                  # end of a train's last mixed step, not fetched yet
    nxt = 0
    ttft, prefill, wait, drained = [], [], [], []
    while nxt < len(arrivals) or handles:
        if nxt < len(arrivals) and arrivals[nxt] <= now:
            batch = []
            while nxt < len(arrivals) and arrivals[nxt] <= now:
                batch.append(arrivals[nxt])
                nxt += 1
            admit = now
            drained.append(len(handles) * chunk_steps)
            while handles:
                now = max(now, handles.popleft())
            for due in batch:
                for _ in range(prompt_steps):
                    now += host_ms
                    device_free = max(now, device_free) + mixed_ms
                ttft.append(device_free - due)
                prefill.append(device_free - admit)
                wait.append(admit - due)
            # One step ahead of the host: every step but the last is fetched while the next runs.
            now = max(now, device_free - mixed_ms)
            train_end = device_free
            continue
        if nxt >= len(arrivals):
            break
        now += host_ms
        device_free = max(now, device_free) + chunk
        handles.append(device_free)
        if train_end is not None:
            now, train_end = max(now, train_end), None
        while len(handles) > depth:
            now = max(now, handles.popleft())
    p = lambda xs, q: sorted(xs)[min(int(len(xs) * q), len(xs) - 1)]  # noqa: E731
    return {"ttft_p50": p(ttft, 0.5), "prefill_p50": p(prefill, 0.5), "queue_wait_p95": p(wait, 0.95),
            "drained_steps_p50": p(drained, 0.5), "drained_steps_p90": p(drained, 0.9),
            "drained_steps_max": max(drained), "requests": len(ttft)}


def over_seeds(seeds: int, **kw) -> dict:
    runs = [simulate(seed=s, **kw) for s in range(seeds)]
    return {k: round(statistics.fmean(r[k] for r in runs), 1) for k in runs[0]}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--table", action="store_true", help="the three open-loop cells at depth 2 / 1 and 8 / 4 / 2 steps")
    ap.add_argument("--chunk-ms", type=float, help="device ms of an 8-step decode chunk")
    ap.add_argument("--mixed-ms", type=float, help="device ms of one mixed step")
    ap.add_argument("--prompt-steps", type=int, default=1, help="mixed steps a prompt")
    ap.add_argument("--rate", type=float, help="offered requests a second (Poisson)")
    ap.add_argument("--depth", type=int, default=1, help="chunks left unfetched behind the running one")
    ap.add_argument("--chunk-steps", type=int, default=8, help="decode steps a chunk")
    ap.add_argument("--host-ms", type=float, default=1.0, help="host work a submit")
    ap.add_argument("--seconds", type=float, default=600.0)
    ap.add_argument("--seeds", type=int, default=3)
    args = ap.parse_args()
    common = dict(host_ms=args.host_ms, seconds=args.seconds)
    if args.table:
        for cell, (chunk_ms, mixed_ms, steps, rate) in CELLS.items():
            for chunk_steps in (8, 4, 2):
                for depth in (2, 1):
                    print(json.dumps({"cell": cell, "depth": depth, "chunk_steps": chunk_steps, **over_seeds(
                        args.seeds, chunk_ms=chunk_ms, mixed_ms=mixed_ms, prompt_steps=steps, rate=rate,
                        depth=depth, chunk_steps=chunk_steps, **common)}))
        return
    if None in (args.chunk_ms, args.mixed_ms, args.rate):
        ap.error("--chunk-ms, --mixed-ms and --rate, or --table")
    print(json.dumps({"depth": args.depth, "chunk_steps": args.chunk_steps, **over_seeds(
        args.seeds, chunk_ms=args.chunk_ms, mixed_ms=args.mixed_ms, prompt_steps=args.prompt_steps,
        rate=args.rate, depth=args.depth, chunk_steps=args.chunk_steps, **common)}))


if __name__ == "__main__":
    main()
