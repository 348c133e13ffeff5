"""How close a mixed step of a ``xing4_0`` configuration comes to the memory
roofline: the bytes a step must read, a lower bound counted from the file's
sizes and the step's own counters (``mla_bytes.step_bytes``: every layer's five
attention matrices and hyper-connection projections, the dense prefix, the
distinct experts its rows chose, the shared expert, the head, the latent pages
its rows' tiles walked at 1,152 B a token), over the chip's peak bandwidth,
divided by the device time of the mixed-step program (``trace_classes.json``
``modules.mixed_step``: its median duration in the trace). The bytes are the
median over the ``mixed`` records dispatched inside the capture, as the time is
a median. A chunk's absorbed attention is bound by the MXU at long contexts
(``mla_bytes``), so a low share there is the chunk's operations and not idle
bandwidth. Nothing for another configuration, without a trace, or where a
record lacks a counter; over 100% is a fault of the count."""
from mla_bytes import is_xing, step_bytes
from stats import dispatched_in_capture, median

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def compute(sources):
    trace, config = sources.get("trace"), sources["config"]
    if not trace or not trace.get("mixed_step_s") or not is_xing(config):
        return None
    records = dispatched_in_capture(sources)
    if records is None:
        return None
    counts = [step_bytes(config, t) for t in records if t.get("kind") == "mixed"]
    if not counts or any(b is None for b in counts):
        return None
    return 100.0 * (median(counts) / sources["chip"]["hbm_bytes_per_s"]) / trace["mixed_step_s"]
