"""The fetch's way back: over the host-stepped steps of the profiler's capture
(a step program that ended while the scheduler thread sat in its fetch
annotation, with nothing queued behind it), the end of the program's event on
the device plane's ``XLA Modules`` line (``jit__mixed_step_fn*`` /
``jit__decode_chunk_fn*``) to the end of the ``engine.mixed.fetch_copy`` /
``engine.chunk.fetch_copy`` annotation on the host; the median
(``idle_ledger.fetch_returns``). The device is idle all that way. Both ends
lie in one file, but its two planes keep two clocks (the device's read 0.3-2.9
ms early on the chip): the device's times are first put on the host's by
causality (``idle_ledger.device_clock_shift``: between two hard bounds, good to
0.1-0.3 ms; the shift and its bounds are in ``<trace>.idle_by_span.json``).
Nothing on a program whose fetch is one stamp."""
from idle_ledger import capture_account

LAYER = "sidecar scheduler"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def compute(sources):
    got = capture_account(sources)
    return None if got is None else got["fetch_return_ms"]
