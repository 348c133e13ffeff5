"""Share of the whole window in which the device had nothing queued while the
scheduler thread emitted or delivered tokens: the sum of ``starved_by``
``emit + deliver`` over every timeline record of the window, over the window's
seconds (ROADMAP S3 a, d). A part of ``host_starved_share``. Nothing on a
program whose records carry no ``starved_by``."""
from idle_ledger import DELIVERY, starved_share

LAYER = "sidecar scheduler"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "timeline"


def compute(sources):
    return starved_share(sources, DELIVERY)
