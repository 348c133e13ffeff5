"""Share of the device's busy time spent in the Mamba-2 layers' state-space
operations of a ``granitemoehybrid`` configuration, decode program and mixed
step together (``mamba2_bytes.scan_pattern``: the chunk kernel by name,
``ssd_chunk_kernel``, and its transposed operands; the one-token update and the
convolution by the results only they have: the state store's and the tails' own
shapes, a row's x or y by head, the packed tokens' Di + 2 N channels). The two
projections have any projection's shapes, the gate and the norm any
activation's, and are not counted: a lower bound. Nine of ten token mixers are
such layers: this puts a number on what they cost beside the expert layers.
Nothing for another configuration, or where the trace holds no such operation
(the parent commit)."""
from mamba2_bytes import is_granite, scan_pattern
from mla_bytes import op_times

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def compute(sources):
    trace, config = sources.get("trace"), sources["config"]
    if not trace or not trace.get("busy_s") or not is_granite(config):
        return None
    times = op_times(trace, "ssd_ops", scan_pattern(config))
    return None if times is None else 100.0 * times["seconds"] / trace["busy_s"]
