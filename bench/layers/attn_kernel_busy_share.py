"""Share of the device's busy time spent in the paged-attention kernels."""

LAYER = "kernels"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def compute(sources):
    trace = sources.get("trace")
    if not trace or not trace.get("busy_s"):
        return None
    return 100.0 * trace["class_s"].get("attention_kernel", 0.0) / trace["busy_s"]
