"""What the path outside the engine adds to time to first token: per request,
client TTFT - (queue_wait_ms + prefill_ms) of the sidecar's access-log line
with the same trace id; the median. Covers the gateway's admission, the
provider and /proxy hop, the SSE relay, and the sidecar's own HTTP handling and
request preparation before the scheduler's clock starts."""
from stats import measured, median, ttft_ms

LAYER = "gateway"
UNIT = "ms"
MOVES = "ttft_p50_ms"
SOURCE = "client+access_log"


def compute(sources):
    log = sources["access_log"]
    diffs = []
    for r in measured(sources):
        ev, t = log.get(r.trace_id), ttft_ms(r)
        if ev is None or t is None or ev.get("queue_wait_ms") is None or ev.get("prefill_ms") is None:
            continue
        diffs.append(t - r.late_s * 1e3 - ev["queue_wait_ms"] - ev["prefill_ms"])
    return median(diffs)
