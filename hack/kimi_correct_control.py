#!/usr/bin/env python3
"""By hand, on the chip: controls of ``kimi-linear-48b-a3b.long-context``'s ``correct``.

``bench/run.py`` leaves a run's probes (what the timed path served) in
``chiprun_out/bench/<cell>/seed<n>-trace<t>/probes.json``. This sends the same
probes through the harness's own comparison, ``bench/reference.py`` as it
stands, with the configuration's file changed in ONE mechanism (ISSUE 54):
(a) the delta rule's correction dropped (u_t = beta_t v_t: a decayed sum of
outer products, what ``ops/ssd.py``'s form of a state layer would compute);
(b) one decay a head in place of one a channel (a head's channels all take the
mean of their log decays: a scalar-decay kernel's model); (c) every KDA state
zero in front of every 512th position: a state lost at every chunk boundary of
the cell's mixed step; (d) the ABSENT experts computed: a chosen expert that
this chip does not hold gets the weights of the held expert at its index modulo
128, what a program that put something in place of the other chip's part would
add. A control has to come out NOT ok (exit code 1 from the child): if it reads
ok, the comparison cannot see the mechanism. ``sound`` runs the file unchanged,
which has to read ok.

    python3 hack/kimi_correct_control.py <run's out_dir> [--controls sound a b c d] [--rehearse-cpu]

(``--rehearse-cpu``: a rehearsal's probes are under 512 tokens, so (c) there
restarts every 36.)
"""
import sys
from pathlib import Path

from trinity_correct_control import run

ROOT = Path(__file__).resolve().parent.parent
CONFIG = ROOT / "bench" / "configs" / "kimi-linear-48b-a3b-int8.json"
CONTROLS = {
    "sound": {},
    "a": {"assumed_mechanisms": {"delta_rule": False}},
    "b": {"assumed_mechanisms": {"decay_per_channel": False}},
    "c": {"assumed_mechanisms": {"state_restart_every": 36 if "--rehearse-cpu" in sys.argv else 512}},
    "d": {"assumed_mechanisms": {"absent_experts": "computed"}},
}


if __name__ == "__main__":
    sys.exit(run(CONFIG, CONTROLS, ["a", "b", "c", "d"], __doc__))
