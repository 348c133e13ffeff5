"""Share of the device's busy time spent in the expert layer's two kernels
(``moe_gate_up_gmm``, ``moe_down_gmm``: every such event of the trace's
operation line, ``moe_bytes.expert_kernel_times``), decode and mixed step
together. Nothing where the trace holds no such operation."""
from moe_bytes import expert_kernel_times

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def compute(sources):
    trace = sources.get("trace")
    if not trace or not trace.get("busy_s"):
        return None
    times = expert_kernel_times(trace)
    return None if times is None else 100.0 * times["all_s"] / trace["busy_s"]
