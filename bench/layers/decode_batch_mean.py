"""Mean rows in a decode step: the timeline's ``batch`` of decode records,
weighted by their ``steps``."""

LAYER = "sidecar scheduler"
UNIT = "rows"
MOVES = "tokens_per_s"
SOURCE = "timeline"


def compute(sources):
    recs = [t for t in sources["timeline"] if t.get("kind") == "decode" and t.get("steps")]
    steps = sum(t["steps"] for t in recs)
    return sum(t["batch"] * t["steps"] for t in recs) / steps if steps else None
