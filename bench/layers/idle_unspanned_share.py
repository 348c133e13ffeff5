"""How much of the device's idle time inside the profiler's capture fell while
the scheduler thread had NO annotation of its own open but ``sched.loop``: idle
seconds (gaps of at least ``gap_floor_s`` between operations, as
``breakdown.idle_gaps`` takes them) under no span, over all such idle seconds
(``idle_ledger.account``, the device's times put on the host's clock first;
by span in ``<trace>.idle_by_span.json``). Measured
where ``idle_unexplained_share`` infers. High means work on the scheduler
thread that no span names. Nothing on a program without the idle ledger."""
from idle_ledger import capture_account

LAYER = "device"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace+annotations"


def compute(sources):
    got = capture_account(sources)
    if got is None or not got["idle_s"]:
        return None
    return 100.0 * got["unspanned_s"] / got["idle_s"]
