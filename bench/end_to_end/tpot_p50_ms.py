"""Per request (last content frame - first) / (tokens - 1); median over requests."""
from stats import measured, median, tpot_ms

UNIT = "ms"
SOURCE = "client"


def compute(sources):
    return median([t for t in map(tpot_ms, measured(sources)) if t is not None])
