"""Share of the device's busy time spent in the gated short convolutions of an
``lfm2_moe`` configuration, in the decode program and in the mixed step
together (``lfm2_bytes.shortconv_times``: the operations that hold the input
projection's 3 x hidden columns or the state store's own shapes, or a kernel
named ``shortconv_*``). The output projection has the shape of the attention's
q and o projections and is not counted: a lower bound. 18 of LFM2-8B-A1B's 24
token mixers are such layers and 3% of a decode step's bytes: their cost is
launches and small fusions between the expert layers, which this puts a number
on. Nothing for another configuration, or where the trace holds no such
operation."""
from lfm2_bytes import shortconv_times

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def compute(sources):
    trace = sources.get("trace")
    if not trace or not trace.get("busy_s"):
        return None
    times = shortconv_times(trace, sources["config"])
    return None if times is None else 100.0 * times["shortconv_s"] / trace["busy_s"]
