#!/usr/bin/env python3
"""``bench/run.py`` with the sidecar's timeline kept: a by-hand wrapper (ISSUE 38).

    python3 hack/timeline_run.py [--root <checkout>] --workload <name> --seed <n> --seconds <s> --trace <0|1>

The harness reads ``/debug/timeline`` in traced runs only and keeps it nowhere
(``PERF.md`` section 7 c: a ``benchmark`` issue's to change), so the whole
window's account by cause (``starved_by``) of an UNTRACED run, and the records
before and after a capture in a traced one, have to be fetched by hand. This
runs the checkout's own ``bench/run.py`` unchanged but for one seam: when the
run reads the sidecar's account (``procs.sidecar_account``, once at the start
and once after the window), the timeline's ring, ``status.jax_trace``,
``status.scheduler``, ``status.startup`` and the compile ledger
(``status.device.compile``: ISSUE 49's ``recompile_events``) are saved as
``<run dir>/timeline.json`` with where the window lay, cut as the traced run
cuts it. ``python3 bench/idle_ledger.py
<run dir>/timeline.json`` then prints the table by cause. Stays off JAX, as
``run.py`` does.
"""
import json
import sys
import time
from pathlib import Path

argv = sys.argv[1:]
root = Path(argv[argv.index("--root") + 1]).resolve() if "--root" in argv else Path(__file__).resolve().parent.parent
if "--root" in argv:
    del argv[argv.index("--root"):argv.index("--root") + 2]
sys.argv = [str(root / "bench" / "run.py"), *argv]
sys.path.insert(0, str(root / "bench"))

import procs  # noqa: E402
import run  # noqa: E402

window: dict = {}
account, say = procs.sidecar_account, run.say


def saying(**fields):
    if fields.get("event") == "start":
        window["dir"] = str(run.ROOT / "chiprun_out" / "bench" / fields["workload"] /
                            f"seed{fields['seed']}-trace{fields['trace']}")
        window["seconds"] = fields["seconds"]
    if fields.get("event") == "window opens":   # said right after the opening was fixed: ramp + 0.05 s ahead
        window["epoch_open"] = time.time() + fields["ramp_s"] + 0.05
    say(**fields)


def accounting(sport, platform, chips):
    status = account(sport, platform, chips)
    _, doc, _ = procs.http_json(sport, "GET", "/debug/timeline")
    if doc and "dir" in window:
        Path(window["dir"], "timeline.json").write_text(json.dumps(
            {**window, "jax_trace": status.get("jax_trace"), "scheduler": status.get("scheduler"),
             "startup": status.get("startup"), "compile": (status.get("device") or {}).get("compile"),
             "entries": doc.get("entries", [])}))
    return status


run.say, procs.sidecar_account = saying, accounting
sys.exit(run.main())
