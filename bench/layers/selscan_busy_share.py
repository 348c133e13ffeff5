"""Share of the device's busy time spent in the Mamba-1 layers' state-space
operations of a ``jamba`` configuration, decode program and mixed step together:
the depthwise convolution, the chunk kernel and the one-token update of every
Mamba layer (``mamba1_bytes.scan_pattern``: a kernel named ``ssm_*``, or an
operation that touches the state store's own shapes: the SAME operations
``ssm_busy_share`` reads for the other Mamba-1 model, at the same four sizes, so
the two cells compare). The mixers' other elementwise work is not seen: a lower
bound. 26 of 28 token mixers are such layers: this puts a number on what they
cost beside the MLPs. Nothing for another configuration, or where the trace holds
no such operation (the parent commit)."""
from mamba1_bytes import is_jamba, scan_pattern
from mla_bytes import op_times

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def compute(sources):
    trace, config = sources.get("trace"), sources["config"]
    if not trace or not trace.get("busy_s") or not is_jamba(config):
        return None
    times = op_times(trace, "selscan_ops", scan_pattern(config))
    return None if times is None else 100.0 * times["seconds"] / trace["busy_s"]
