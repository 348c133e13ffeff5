"""Share of the whole window in which the device had nothing queued and the
host's own account cannot say what for: the sum of ``starved_by``
``loop + wake + record`` over every timeline record of the window, over the
window's seconds: what lay under no span (``loop``), the way from an arrival to
the scheduler thread awake (``wake``) and what the instruments themselves cost
(``record``). A part of ``host_starved_share``; the submit's own phases are
that share less this one, ``starved_slot_share`` and
``starved_delivery_share``. Nothing on a program whose records carry no
``starved_by``."""
from idle_ledger import UNSPANNED, starved_share

LAYER = "sidecar scheduler"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "timeline"


def compute(sources):
    return starved_share(sources, UNSPANNED)
