"""95th percentile of the scheduler's queue wait (submit -> admit), access log."""
from stats import measured, p95

LAYER = "sidecar scheduler"
UNIT = "ms"
MOVES = "ttft_p50_ms"
SOURCE = "access_log"


def compute(sources):
    log = sources["access_log"]
    waits = [log[r.trace_id]["queue_wait_ms"] for r in measured(sources)
             if r.trace_id in log and log[r.trace_id].get("queue_wait_ms") is not None]
    return p95(waits)
