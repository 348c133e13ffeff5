"""Rows of the page pool an EVA model's steps read, as a share of the tokens of context
full attention would read: 100 x sum ``eva_rows`` / sum ``eva_context`` over the
window's ``decode`` and ``mixed`` records of ``/debug/timeline``, the program's own
counts (``eva_bytes.read_share``). A row in window w reads 128 w summaries and its
window's tokens so far, so a context of 2-6 windows reads 3-55% of itself and a
context inside the first window 100%. Nothing where no record carries the counts."""
from eva_bytes import read_share

LAYER = "engine and model step"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "program_counter"


def compute(sources):
    return read_share(sources.get("timeline"))
