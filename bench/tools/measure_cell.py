#!/usr/bin/env python3
"""Measure one cell as the builder's contract asks: two sets of N runs with the
same seeds in both, a traced run after each of the first ``--traced`` sets; print each metric's median and
spread per set. A spread is (Q3 - Q1) / median with statistics.quantiles(n=4).

    python3 bench/tools/measure_cell.py --workload <name> [--runs 6] [--traced 2] [--seconds S]

Runs on the machine it is started on (under the chip tool, by hand). Results go
to chiprun_out/measure/<workload>.json; every run's last line is kept.
"""

from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent.parent
SEEDS = [2147483659, 2147483777, 1999999973, 1500000001, 1000000007, 500000003, 2147483783, 1234567891]


def one(workload: str, seed: int, seconds: float | None, trace: int) -> dict:
    argv = [sys.executable, str(ROOT / "bench" / "run.py"), "--workload", workload, "--seed", str(seed),
            "--trace", str(trace)] + (["--seconds", str(seconds)] if seconds else [])
    t0 = time.monotonic()
    out = subprocess.run(argv, cwd=ROOT, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    lines = out.stdout.decode(errors="replace").strip().splitlines()
    rec = {"seed": seed, "trace": trace, "rc": out.returncode, "wall_s": time.monotonic() - t0}
    try:
        rec["result"] = json.loads(lines[-1])
    except (ValueError, IndexError):
        rec["tail"] = lines[-6:]
    for line in lines:
        if '"window closed"' in line or '"reference"' in line:
            try:
                d = json.loads(line)
                rec[d["event"]] = {k: v for k, v in d.items() if k not in ("event",)}
            except ValueError:
                pass
    print(json.dumps({k: v for k, v in rec.items() if k not in ("window closed", "reference")})[:1500], flush=True)
    return rec


def spread(values: list[float]) -> float | None:
    if len(values) < 3:
        return None
    q1, _, q3 = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q3 - q1) / med if med else None


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--traced", type=int, default=2)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--tag", default="", help="suffix of the result file's name, to keep an earlier one")
    args = ap.parse_args()
    seeds = SEEDS[: args.runs]
    sets = []
    traced = []
    for s in range(args.sets):
        sets.append([one(args.workload, seed, args.seconds, 0) for seed in seeds])
        if s < args.traced:
            traced.append(one(args.workload, seeds[s], args.seconds, 1))
    def metrics(r: dict) -> dict:
        return (r.get("result") or {}).get("metrics") or {}

    names = sorted({m for st in sets for r in st for m in metrics(r)})
    table = {}
    for m in names:
        rows = []
        for st in sets:
            vals = [metrics(r)[m]["value"] for r in st if m in metrics(r)]
            # the first run of the first set compiled: the contract keeps its set-up apart
            rows.append({"n": len(vals), "median": statistics.median(vals) if vals else None,
                         "spread": spread(vals), "values": vals})
        table[m] = rows
        print(m, [(r["n"], r["median"], r["spread"]) for r in rows], flush=True)
    out = {"workload": args.workload, "seeds": seeds, "table": table, "sets": sets, "traced": traced}
    dest = ROOT / "chiprun_out" / "measure"
    dest.mkdir(parents=True, exist_ok=True)
    (dest / f"{args.workload}{args.tag}.json").write_text(json.dumps(out))
    bad = [r for st in sets + [traced] for r in st if r["rc"] != 0 or not (r.get("result") or {}).get("correct")]
    print(json.dumps({"runs": sum(map(len, sets)) + len(traced), "not_correct": len(bad)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
