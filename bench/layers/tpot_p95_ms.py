"""Per request (last content frame - first) / (tokens - 1); 95th percentile over
requests, nothing under 200. A tail (spread 5-7% across seeds, my chip runs,
PR 23): it stands here, beside the median that carries the bound."""
from stats import measured, p95, tpot_ms

LAYER = "engine and model step"
UNIT = "ms"
MOVES = "tpot_p50_ms"
SOURCE = "client"


def compute(sources):
    return p95([t for t in map(tpot_ms, measured(sources)) if t is not None])
