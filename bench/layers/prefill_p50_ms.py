"""Median of the access log's ``prefill_ms``: admit -> first token, on the
scheduler's clock (the mixed steps that carry the prompt's chunks)."""
from stats import measured, median

LAYER = "engine and model step"
UNIT = "ms"
MOVES = "ttft_p50_ms"
SOURCE = "access_log"


def compute(sources):
    log = sources["access_log"]
    return median([log[r.trace_id]["prefill_ms"] for r in measured(sources)
                   if r.trace_id in log and log[r.trace_id].get("prefill_ms") is not None])
