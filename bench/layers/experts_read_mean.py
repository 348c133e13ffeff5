"""Distinct experts a layer reads in a decode step: the program's own count
(``experts_read`` on the ``decode`` records of ``/debug/timeline``, a mean over
the record's steps and the layers), averaged over the window's records by their
steps. Between ``num_experts_per_tok`` (every row chose alike) and
``num_local_experts``. Nothing where no record carries the field."""

from moe_bytes import mean_experts_read

LAYER = "engine and model step"
UNIT = "experts"
MOVES = "tpot_p50_ms"
SOURCE = "program_counter"


def compute(sources):
    return mean_experts_read(sources.get("timeline"))
