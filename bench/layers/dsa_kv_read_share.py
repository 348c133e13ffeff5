"""Share of the cached tokens its queries could see whose keys and values the
attention read: 100 x sum ``dsa_kv_tokens_read`` / sum ``dsa_context_tokens``
over the window's ``decode`` and ``mixed`` records of ``/debug/timeline``, the
program's own counts (both summed over a record's queries, steps and layers:
``dsa_bytes.read_share``). A decode row past topk reads topk tokens of its
context, so its records read topk / context; a prompt chunk's queries are
walked in tiles over every block of keys up to the tile's last position, so its
records read 100 and a little over (whole blocks). Nothing where no record
carries the counts."""
from dsa_bytes import read_share

LAYER = "engine and model step"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "program_counter"


def compute(sources):
    return read_share(sources.get("timeline"))
