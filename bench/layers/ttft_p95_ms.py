"""95th percentile of (first content frame - due time), as the client sees it;
nothing under 200 requests. A tail: between two runs of one seed it moved by up
to 4.6% and across seeds its spread was 5-7% (my chip runs, PR 23), too wide to
carry a bound, so it stands here, beside the median that does."""
from stats import measured, p95, ttft_ms

LAYER = "sidecar scheduler"
UNIT = "ms"
MOVES = "ttft_p50_ms"
SOURCE = "client"


def compute(sources):
    return p95([t for t in map(ttft_ms, measured(sources)) if t is not None])
