#!/usr/bin/env python3
"""By hand, on the chip: controls of ``evabyte-6.5b.long-context``'s ``correct``.

``bench/run.py`` leaves a run's probes (what the timed path served) in
``chiprun_out/bench/<cell>/seed<n>-trace<t>/probes.json``. This sends the same
probes through the harness's own comparison, ``bench/reference.py`` as it
stands, with the configuration's file changed in ONE mechanism (ISSUE 61):
(a) the summaries left out (a query sees its own window alone: a sliding window
that restarts); (b) a window's summaries seen one window early (the query's own
complete chunks as summaries beside their exact keys); (c) the pooling a plain
mean of the chunk (what mu = phi = 0 would give); (d) a roll that was missed:
in place of each earlier window's summaries the first 128 EXACT rows of that
window, which is what its page held before the fold. Then the reference one
step of precision below what the configuration serves at: (e) the stream, the
projections and the summaries rounded to bf16 wherever the served path holds
them in bf16 (the served path's own precision, so it reads near ``sound``: it
says how much of a sound reading is rounding); (f) the int8 matrices rounded
again to e4m3 under one scale a column. A control has to come out NOT ok (exit
code 1 from the child): if it reads ok, the comparison cannot see the mechanism.
``sound`` runs the file unchanged, which has to read ok.

    python3 hack/eva_correct_control.py <run's out_dir> [--controls sound a b c d e f] [--rehearse-cpu]
"""
import sys
from pathlib import Path

from trinity_correct_control import run

ROOT = Path(__file__).resolve().parent.parent
CONFIG = ROOT / "bench" / "configs" / "evabyte-6.5b-int8.json"
CONTROLS = {
    "sound": {},
    "a": {"assumed_mechanisms": {"summaries": "none"}},
    "b": {"assumed_mechanisms": {"summaries": "one_window_early"}},
    "c": {"assumed_mechanisms": {"pooling": "mean"}},
    "d": {"assumed_mechanisms": {"summaries": "stale_rows"}},
    "e": {"assumed_mechanisms": {"precision": "bfloat16"}},
    "f": {"assumed_mechanisms": {"precision": "e4m3"}},
}


if __name__ == "__main__":
    sys.exit(run(CONFIG, CONTROLS, ["a", "b", "c", "d", "e", "f"], __doc__))
