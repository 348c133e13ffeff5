"""Share of the device's busy time spent scoring, selecting and reading for the
learned sparse attention, in the decode program and in the mixed step together
(``dsa_bytes.dsa_times``: the operations that touch the shapes only that stage
has, or a kernel named ``dsa_*``). Nothing for a configuration without an
indexer, or where the trace holds no such operation."""
from dsa_bytes import dsa_times

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def compute(sources):
    trace = sources.get("trace")
    if not trace or not trace.get("busy_s"):
        return None
    times = dsa_times(trace, sources["config"])
    return None if times is None else 100.0 * times["dsa_s"] / trace["busy_s"]
