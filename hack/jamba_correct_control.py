#!/usr/bin/env python3
"""By hand, on the chip: controls of ``jamba2-3b.long-context``'s ``correct``.

``bench/run.py`` leaves a run's probes (what the timed path served) in
``chiprun_out/bench/<cell>/seed<n>-trace<t>/probes.json``. This sends the same
probes through the harness's own comparison, ``bench/reference.py`` as it
stands, with the configuration's file changed in ONE mechanism (ISSUE 64): (a)
the state kept in bfloat16 (rounded after every token, what a store of that type
holds); (b) the three inner norms left out (dt, B and C as W_x gives them); (c)
a slot's state NOT zeroed at position 0 (every mixer starts from the state the
same sequence left at its end: what the second of two twin requests would be
served from); (c512) the state lost at every chunk boundary of 512. A control
has to come out NOT ok (exit code 1 from the child): if it reads ok, the
comparison cannot see the mechanism. ``sound`` runs the file unchanged, which
has to read ok.

    python3 hack/jamba_correct_control.py <run's out_dir> [--controls sound a b c c512] [--rehearse-cpu]
"""
import sys
from pathlib import Path

from trinity_correct_control import run

ROOT = Path(__file__).resolve().parent.parent
CONFIG = ROOT / "bench" / "configs" / "jamba2-3b-int8.json"
CONTROLS = {
    "sound": {},
    "a": {"assumed_mechanisms": {"state_dtype": "bfloat16"}},
    "b": {"assumed_mechanisms": {"inner_norms": False}},
    "c": {"assumed_mechanisms": {"state_from_previous": True}},
    "c512": {"assumed_mechanisms": {"state_restart_every": 512}},
}


if __name__ == "__main__":
    sys.exit(run(CONFIG, CONTROLS, ["a", "b", "c"], __doc__))
