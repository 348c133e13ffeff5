"""Share of the device's busy time spent in the state-space operations: the
depthwise convolution and the selective scan of every Mamba layer, in the decode
program and in the mixed step together (``hybrid_bytes.ssm_times``: the
operations that touch the state store's own shapes, or a kernel named
``ssm_*``). Nothing for a configuration without a state, or where the trace
holds no such operation."""
from hybrid_bytes import ssm_times

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def compute(sources):
    trace = sources.get("trace")
    if not trace or not trace.get("busy_s"):
        return None
    times = ssm_times(trace, sources["config"])
    return None if times is None else 100.0 * times["ssm_s"] / trace["busy_s"]
