"""Share of the device's busy time spent in the Kimi Delta Attention layers'
state operations of a ``kimi_linear`` configuration, decode program and mixed
step together (``kda_bytes.scan_pattern``: the chunk kernel and the one-token
update by name, ``kda_chunk_kernel`` and ``kda_state_update_kernel``, the chunk
kernel's padded operands, and the convolution by the results only it has: the
tails' own shapes and the packed tokens' 3 P channels). The projections have
any projection's shapes, the gate and the norm any activation's, and are not
counted: a lower bound. Six of eight token mixers are such layers: this puts a
number on what they cost beside the expert layers. Nothing for another
configuration, or where the trace holds no such operation (the parent commit)."""
from kda_bytes import is_kimi, scan_pattern
from mla_bytes import op_times

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def compute(sources):
    trace, config = sources.get("trace"), sources["config"]
    if not trace or not trace.get("busy_s") or not is_kimi(config):
        return None
    times = op_times(trace, "kda_ops", scan_pattern(config))
    return None if times is None else 100.0 * times["seconds"] / trace["busy_s"]
