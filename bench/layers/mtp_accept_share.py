"""Share of the next-token module's drafts that the model accepted: the
program's own counts (``mtp_accepted`` over ``mtp_drafted`` on the window's
``decode`` and ``mixed`` records of ``/debug/timeline``, each a sum over the
record's rounds and rows). At seeded weights it is near what two unrelated
distributions over the 95 biased ids share; a trained checkpoint's is far
higher. Nothing where no record drafted (another configuration, the parent
commit)."""
from mtp_bytes import drafting

LAYER = "engine and model step"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "program_counter"


def compute(sources):
    d = drafting(sources.get("timeline"))
    return None if d is None else 100.0 * d["accepted"] / d["drafted"]
