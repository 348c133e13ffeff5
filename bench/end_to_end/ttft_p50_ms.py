"""Median over the requests due in the window of (first content frame - due time)."""
from stats import measured, median, ttft_ms

UNIT = "ms"
SOURCE = "client"


def compute(sources):
    return median([t for t in map(ttft_ms, measured(sources)) if t is not None])
