#!/usr/bin/env python3
"""By hand, on the chip: controls of ``granite-4.0-h-small.long-context``'s ``correct``.

``bench/run.py`` leaves a run's probes (what the timed path served) in
``chiprun_out/bench/<cell>/seed<n>-trace<t>/probes.json``. This sends the same
probes through the harness's own comparison, ``bench/reference.py`` as it
stands, with the configuration's file changed in ONE mechanism (ISSUE 50):
(a) every mixer's state zero in front of the seventh token from a probe's end:
what a program that lost a slot's state between the step that ends its prompt
(the chunk kernel's S_out) and its first decode step (the one-token update)
would compute, a probe's eight answers being its prompt's last logits and seven
fed back; (b) the gate dropped from the mixer's norm (rmsnorm(y) for rmsnorm(y
silu(z))); (c) ``residual_multiplier`` 1 for 0.22; (d) the shared MLP dropped
from every layer's sum. A control has to come out NOT ok (exit code 1 from the
child): if it reads ok, the comparison cannot see the mechanism. ``sound`` runs
the file unchanged, which has to read ok. ``a512`` zeroes the state in front of
every 512th position, a state lost at every chunk boundary but the last: a
probe's last such boundary lies 320-500 tokens in front of its first compared
token, and the published initialisation's median head forgets in 1 / (Delta A)
= 25 tokens (the slowest in 1,000), so it was expected to read ok; it reads NOT
ok, 0.64 / 0.205 nats against limits of 0.45 / 0.12 and a sound 0.13 / 0.034
(my chip run, PR 50; PERF.md section 6): the few slow heads carry enough of what
the state holds.

    python3 hack/granite_correct_control.py <run's out_dir> [--controls sound a b c d a512] [--rehearse-cpu]
"""
import sys
from pathlib import Path

from trinity_correct_control import run

ROOT = Path(__file__).resolve().parent.parent
CONFIG = ROOT / "bench" / "configs" / "granite-4.0-h-small-int8.json"
CONTROLS = {
    "sound": {},
    "a": {"assumed_mechanisms": {"state_restart_from_end": 7}},
    "b": {"assumed_mechanisms": {"norm_gate": False}},
    "c": {"residual_multiplier": 1.0},
    "d": {"assumed_mechanisms": {"shared_mlp": False}},
    "a512": {"assumed_mechanisms": {"state_restart_every": 512}},
}


if __name__ == "__main__":
    sys.exit(run(CONFIG, CONTROLS, ["a", "b", "c", "d"], __doc__))   # (a512: on the chip; a rehearsal's probes are under 512)
