#!/usr/bin/env python3
"""Find the knee of an open-loop cell: one server, rates rising step by step.

    python3 bench/sweep.py --workload <name> --start <req/s> [--steps 8] [--step-seconds 20]
                           [--factor 1.25] [--seed 1] [--rehearse-cpu]

Run once, by hand, when a cell is defined (or when an optimisation has moved
its capacity); the benchmark itself never searches. Each step offers the cell's
traffic mix at a fixed rate for ``--step-seconds``; the next step follows at
once at ``--factor`` times the rate. A step *holds* when the requests completed
in it are within 3% of those offered (after excusing the growth in requests in
flight that the higher rate alone brings: rate step x median time in system)
and the access log's queue wait over the step's last third is not more than
twice that of its first third (or under 50 ms, below which a doubling is noise). The knee is the highest rate of the
unbroken run of steps that hold; the cell's ``rate_rps`` is 0.8 of it, written
by hand into bench/cells/<name>.json beside ``knee_rps`` and the date.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import random
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import client  # noqa: E402
import procs  # noqa: E402
import run as bench_run  # noqa: E402
import stats  # noqa: E402
import traffic  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--start", type=float, required=True)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--step-seconds", type=float, default=20.0)
    ap.add_argument("--factor", type=float, default=1.25)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()

    c = bench_run.load_cell(args.workload, args.rehearse_cpu)
    config, mix, model = c["config"], c["mix"], c["model"]
    check = procs.check
    check(mix["loop"] == "open", "only an open-loop cell has a knee")
    out_dir = bench_run.ROOT / "chiprun_out" / "bench" / c["cell"]["name"] / "sweep"
    children = procs.Children(out_dir)
    try:
        sysd = bench_run.start_system(children, c, args.rehearse_cpu)
        warmed = bench_run.warm_up(sysd["gport"], model, mix, config, args.seed)
        check(not warmed["faults"], f"warm-up: {warmed['faults']}")
        ids, value = bench_run.bias_of(config)
        rng = random.Random(args.seed)
        S = args.step_seconds
        rates = [args.start * args.factor ** k for k in range(args.steps)]
        reqs, step_of = [], []
        for k, rate in enumerate(rates):
            for r in traffic.open_loop(mix, rate, S, args.seed + k, stream=f"sweep{k}"):
                reqs.append(traffic.Req(index=len(reqs), due_s=r.due_s + k * S,
                                        messages=r.messages, max_tokens=r.max_tokens, seed=r.seed))
                step_of.append(k)
        docs = [traffic.body(r, model, mix["sampling"], ids, value) for r in reqs]
        tids = [client.new_trace_id(rng) for _ in reqs]
        t_open = time.monotonic() + 0.5
        results = asyncio.run(client.run_open(sysd["gport"], reqs, docs, tids, t_open,
                                              S * len(rates), bench_run.DRAIN_S))
        children.stop(sysd["gateway"])
        children.stop(sysd["sidecar"])
        access = {e["trace_id"]: e for e in children.log_records("sidecar")
                  if e.get("log") == "access" and e.get("trace_id")}

        rows, knee, unbroken = [], None, True
        for k, rate in enumerate(rates):
            mine = [r for r, s in zip(results, step_of) if s == k]
            lo, hi = t_open + k * S, t_open + (k + 1) * S
            done = sum(1 for r in results if r.done_t is not None and lo <= r.done_t < hi)
            waits = lambda a, b: [access[r.trace_id]["queue_wait_ms"] for r in mine  # noqa: E731
                                  if r.trace_id in access and lo + a * S <= r.due_t < lo + b * S
                                  and access[r.trace_id].get("queue_wait_ms") is not None]
            first, last = stats.median(waits(0, 1 / 3)), stats.median(waits(2 / 3, 1))
            ttfts = [t for t in map(stats.ttft_ms, mine) if t is not None]
            tpots = [t for t in map(stats.tpot_ms, mine) if t is not None]
            # Requests in flight grow with the rate even where the system keeps up
            # (Little's law: rate x time in system), so that growth is excused.
            durations = [r.done_t - r.due_t for r in mine if r.done_t is not None]
            excused = (rate - (rates[k - 1] if k else 0.0)) * (stats.median(durations) or 0.0)
            holds = (len(mine) - done <= 0.03 * len(mine) + excused and first is not None
                     and last is not None and (last <= 2 * first or last < 50.0)
                     and not any(r.error for r in mine))
            unbroken = unbroken and holds
            if unbroken:
                knee = rate
            rows.append({"step": k, "rate_rps": rate, "offered": len(mine), "completed_in_step": done,
                         "failed": sum(1 for r in mine if r.error), "excused_in_flight": excused, "queue_wait_first_third_ms": first,
                         "queue_wait_last_third_ms": last, "ttft_p50_ms": stats.median(ttfts),
                         "ttft_p95_ms": stats.percentile(ttfts, 95), "tpot_p50_ms": stats.median(tpots),
                         "tpot_p95_ms": stats.percentile(tpots, 95),
                         "tokens_per_s": sum(n for r in results for t, n in r.frames if lo <= t < hi) / S,
                         "holds": holds})
            print(json.dumps(rows[-1]), flush=True)
        out = {"workload": c["cell"]["name"], "knee_rps": knee,
               "rate_rps_at_0.8": None if knee is None else 0.8 * knee,
               "generator_late": client.lateness(results), "steps": rows,
               "accelerator": sysd["accelerator"]}
        out_dir.mkdir(parents=True, exist_ok=True)
        (out_dir / "sweep.json").write_text(json.dumps(out, indent=1))
        print(json.dumps({k: v for k, v in out.items() if k != "steps"}), flush=True)
    except procs.BenchFailure as e:
        print(json.dumps({"event": "failed", "error": str(e)}), flush=True)
        return 1
    finally:
        children.stop_all()
    return 0


if __name__ == "__main__":
    sys.exit(main())
