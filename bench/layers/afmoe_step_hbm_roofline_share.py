"""How close a mixed step of an ``afmoe`` configuration comes to the memory
roofline: the bytes a step must read, a lower bound counted from the file's
sizes and the step's own counters (``afmoe_bytes.step_bytes``: the distinct
experts its rows chose, the shared experts, the dense prefix, the attention
matrices with the gate, the head, the pages its windowed layers read and its
global layers hold), over the chip's peak bandwidth, divided by the device time
of the mixed-step program (``trace_classes.json`` ``modules.mixed_step``: its
median duration in the trace). The bytes are the median over the ``mixed``
records dispatched inside the capture, as the time is a median. Memory-bound by
the count alone: a 512-token chunk's matrix products (0.7 TFLOP a step) take 4
ms at the chip's bf16 peak, the bytes 9. Nothing for another configuration,
without a trace, or where a record lacks a counter; over 100% is a fault of the
count."""
from afmoe_bytes import is_afmoe, step_bytes
from stats import dispatched_in_capture, median

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def compute(sources):
    trace, config = sources.get("trace"), sources["config"]
    if not trace or not trace.get("mixed_step_s") or not is_afmoe(config):
        return None
    records = dispatched_in_capture(sources)
    if records is None:
        return None
    mixed = [t for t in records if t.get("kind") == "mixed"]
    counts = [step_bytes(config, t) for t in mixed]
    if not counts or any(b is None for b in counts):
        return None
    return 100.0 * (median(counts) / sources["chip"]["hbm_bytes_per_s"]) / trace["mixed_step_s"]
