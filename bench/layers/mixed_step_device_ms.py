"""Device time of one mixed step: the mixed-step program's median duration in
the trace. The unit of prefill: a prompt takes one of these per 512 tokens, and
every decode row in flight waits one out for a single token."""

LAYER = "engine and model step"
UNIT = "ms"
MOVES = "ttft_p50_ms"
SOURCE = "device_trace"


def compute(sources):
    trace = sources.get("trace")
    if not trace or not trace.get("mixed_step_s"):
        return None
    return trace["mixed_step_s"] * 1e3
