"""Tokens a decoding row emits a round: the program's own counts (``mtp_tokens``
over ``mtp_drafted`` on the window's ``decode`` and ``mixed`` records: a row that
verified a draft emitted one token, or two where it was accepted; a budget or a
stop id at the first discards the second). Between 1 and 2. Nothing where no
record drafted."""
from mtp_bytes import drafting

LAYER = "engine and model step"
UNIT = "tokens"
MOVES = "tokens_per_s"
SOURCE = "program_counter"


def compute(sources):
    d = drafting(sources.get("timeline"))
    return None if d is None else d["tokens"] / d["drafted"]
