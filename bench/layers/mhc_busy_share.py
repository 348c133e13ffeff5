"""Share of the device's busy time spent in the hyper-connections of a
``xing4_0`` configuration, decode program and mixed step together
(``mla_bytes.hyper_pattern``: the operations that hold the hc_mult streams a
token, a block's projection of them, or the maps with the tokens in the lanes:
the projections, the Sinkhorn iterations, the read-in and the write-back of
both blocks of every layer). What the compiler fuses with them (a block's input
norm into the read-in) is counted with them. Nothing for another configuration,
or where the trace holds no such operation (the parent commit)."""
from mla_bytes import hyper_pattern, is_xing, op_times

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def compute(sources):
    trace, config = sources.get("trace"), sources["config"]
    if not trace or not trace.get("busy_s") or not is_xing(config):
        return None
    times = op_times(trace, "mhc_ops", hyper_pattern(config))
    return None if times is None else 100.0 * times["seconds"] / trace["busy_s"]
