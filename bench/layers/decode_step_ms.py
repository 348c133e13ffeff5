"""Host-side time of one decode step: the timeline's ``duration_ms / steps`` of
decode records, median. Each record ends in a fetch from the device, so with
chunks chained two deep this is the step's time as the host meets it; the
device's own step time is in ``decode_hbm_roofline_share``."""
from stats import median

LAYER = "engine and model step"
UNIT = "ms"
MOVES = "tpot_p50_ms"
SOURCE = "timeline"


def compute(sources):
    return median([t["duration_ms"] / t["steps"] for t in sources["timeline"]
                   if t.get("kind") == "decode" and t.get("steps")])
