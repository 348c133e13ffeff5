#!/usr/bin/env python3
"""By hand, on the chip: controls of ``lfm2-8b-a1b.reason``'s ``correct``.

``bench/run.py`` leaves a run's probes (what the timed path served) in
``chiprun_out/bench/<cell>/seed<n>-trace<t>/probes.json``. This sends the same
probes through the harness's own comparison, ``bench/reference.py`` as it
stands, with the configuration's file changed in ONE mechanism (ISSUE 43's
controls): (a) the convolution's two lagged taps zeroed, (b) its B gate dropped,
(c) its C gate dropped, (d) the selection bias dropped from the router's
selection, (e) the q/k norms dropped, (f) the convolution restarted every 8
tokens, the decode chunk: what a program that lost the store's tails between
two launches of a step program would compute (a restart corrupts the two
tokens behind it on each layer, so it has to fall among the compared tokens to
show: each probe's eight generated tokens cross one; ``f512`` restarts every 512
tokens, a prompt chunk's length, which falls hundreds of tokens before any
compared token and is expected to read ok: PERF.md section 6, PR 43). A control
has to come out NOT ok (exit code 1 from the child): if it reads ok, the
comparison cannot see the mechanism. ``sound`` runs the file unchanged, which
has to read ok.

    python3 hack/lfm2_correct_control.py <run's out_dir> [--controls a b c d e f] [--rehearse-cpu]

Prints one line of JSON a control: the child's verdict, its largest and mean
difference and each probe's. Exit code 0 when every control read not ok (and
``sound``, where asked for, ok). ``--rehearse-cpu`` changes the rehearsal's
model block instead and passes the flag on to the child.
"""
import sys
from pathlib import Path

from trinity_correct_control import run

CONFIG = Path(__file__).resolve().parent.parent / "bench" / "configs" / "lfm2-8b-a1b-int8.json"
CONTROLS = {
    "sound": {},
    "a": {"assumed_mechanisms": {"conv_lagged_taps": False}},
    "b": {"assumed_mechanisms": {"conv_b_gate": False}},
    "c": {"assumed_mechanisms": {"conv_c_gate": False}},
    "d": {"assumed_mechanisms": {"selection_bias": "none"}},
    "e": {"assumed_mechanisms": {"qk_norm": False}},
    "f": {"assumed_mechanisms": {"conv_restart_every": 8}},
    "f512": {"assumed_mechanisms": {"conv_restart_every": 512}},
}

if __name__ == "__main__":
    sys.exit(run(CONFIG, CONTROLS, ["a", "b", "c", "d", "e", "f"], __doc__, expected_ok=("sound", "f512")))
