#!/usr/bin/env python3
"""By hand, on the chip: controls of ``openpangu-ultra-moe-718b.reason``'s ``correct``.

``bench/run.py`` leaves a run's probes (what the timed path served) in
``chiprun_out/bench/<cell>/seed<n>-trace<t>/probes.json``. This sends the same
probes through the harness's own comparison, ``bench/reference.py`` as it
stands, with the configuration's file changed in ONE mechanism (ISSUE 56):
(1) the two output norms dropped (x + F(norm x): the DeepSeek-V3 block this one
was made from); (2) the ABSENT experts computed: a chosen expert that this chip
does not hold gets the weights of the held expert at its index modulo 16, what a
program that put something in place of the other fifteen chips' part would add;
(3) every query reading the cache as the query one position in front of it does
(it misses the key at its OWN position: what a round's second query computes if
it is given the first's length; the reference cannot know which served tokens
came from a second query, so every query is shifted); (4) the head over other
rows of the vocabulary: token id t's logit read 9,600 columns (one chip's rows)
further on, cyclically within the held slice (the rows behind 19,199 are on no
chip here). A control has to come out NOT ok (exit code 1 from the child): if it
reads ok, the comparison cannot see the mechanism. ``sound`` runs the file
unchanged, which has to read ok.

    python3 hack/pangu_correct_control.py <run's out_dir> [--controls sound 1 2 3 4] [--rehearse-cpu]

(``--rehearse-cpu``: the rehearsal's slice holds 256 rows, so (4) there reads 128 further on.)
"""
import sys
from pathlib import Path

from trinity_correct_control import run

ROOT = Path(__file__).resolve().parent.parent
CONFIG = ROOT / "bench" / "configs" / "openpangu-ultra-moe-718b-int8.json"
CONTROLS = {
    "sound": {},
    "1": {"assumed_mechanisms": {"output_norms": False}},
    "2": {"assumed_mechanisms": {"absent_experts": "computed"}},
    "3": {"assumed_mechanisms": {"own_key": False}},
    "4": {"assumed_mechanisms": {"head_first_row": 128 if "--rehearse-cpu" in sys.argv else 9600}},
}


if __name__ == "__main__":
    sys.exit(run(CONFIG, CONTROLS, ["1", "2", "3", "4"], __doc__))
