"""Share of the device's busy time spent folding windows into pages of summaries, in
the decode program and in the mixed step together (``eva_bytes.fold_times``: the
operations that touch the shapes only the fold has: a window's rows, a window by
chunks, the summaries by head). 0 where no row started a window inside the capture
(a row folds once per 2,048 tokens). Nothing for another configuration or without a
trace."""
from eva_bytes import fold_times

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def compute(sources):
    trace = sources.get("trace")
    if not trace or not trace.get("busy_s"):
        return None
    times = fold_times(trace, sources["config"])
    return None if times is None else 100.0 * times["fold_s"] / trace["busy_s"]
