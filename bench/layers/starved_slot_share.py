"""Share of the whole window in which the device had nothing queued while the
scheduler thread registered or released a slot: the sum of ``starved_by``
``register_slot + release_slot + release`` over every timeline record of the
window, over the window's seconds (ROADMAP S3 b's lever). A part of
``host_starved_share``. Nothing on a program whose records carry no
``starved_by``."""
from idle_ledger import SLOT, starved_share

LAYER = "sidecar scheduler"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "timeline"


def compute(sources):
    return starved_share(sources, SLOT)
