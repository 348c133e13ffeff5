#!/usr/bin/env python3
"""By hand, on the chip: controls of the long-context cell's ``correct``.

``bench/run.py`` leaves a run's probes (what the timed path served) in
``chiprun_out/bench/<cell>/seed<n>-trace<t>/probes.json``. This sends the same
probes through the harness's own comparison, ``bench/reference.py`` as it
stands, with the configuration's file changed in one number: ``sa_config.topk``.
At ``--topk 1000000`` the reference attends over every key where the served
path selected; at half the published topk it keeps half of each set. A control
has to come out NOT ok (exit code 1 from the child): if it reads ok, the
comparison cannot see the selection (PERF.md section 6, PR 36).

    python3 hack/keye_correct_control.py <run's out_dir> [--topk 1000000 1024]

Prints one line of JSON a control: the child's verdict, its largest and mean
difference and each probe's. Exit code 0 when every control read not ok.
"""
import argparse
import json
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
CONFIG = ROOT / "bench" / "configs" / "keye-vl2-30b-a3b-int8.json"


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("out_dir", type=Path)
    ap.add_argument("--topk", type=int, nargs="+", default=[10 ** 6, 1024])
    args = ap.parse_args()
    config = json.loads(CONFIG.read_text())
    seen = True
    for topk in args.topk:
        variant = args.out_dir / f"control-topk{topk}.json"
        variant.write_text(json.dumps({**config, "sa_config": {**config["sa_config"], "topk": topk}}))
        out = subprocess.run([sys.executable, str(ROOT / "bench" / "reference.py"), str(variant),
                              str(args.out_dir / "probes.json")], capture_output=True, text=True,
                             timeout=float(config["reference_limit_s"]))
        lines = out.stdout.strip().splitlines()
        result = json.loads(lines[-1]) if lines else {"error": out.stderr[-1500:]}
        print(json.dumps({"control_topk": topk, "rc": out.returncode, "ok": result.get("ok"),
                          "max_abs_diff": result.get("max_abs_diff"), "mean_abs_diff": result.get("mean_abs_diff"),
                          "probes": [(p.get("prompt_tokens"), p.get("max_abs_diff"), p.get("mean_abs_diff"))
                                     for p in result.get("probes", [])],
                          "seconds": result.get("seconds"), "error": result.get("error")}), flush=True)
        seen = seen and out.returncode == 1 and result.get("ok") is False
    return 0 if seen else 1


if __name__ == "__main__":
    sys.exit(main())
