"""Share of the chip's bf16 peak that a mixed step of a ``kimi_linear``
configuration's matrix products reach: the operations of the tokens a step
carried (``kda_bytes.mixed_step_flops``: every packed token's projections in
six KDA and two latent layers, the dense layer's MLP and the shared expert, the
HELD experts' rows by the record's own count, the chunk form's block products
by ``kda_blocks``, the latent read over each row's context counted as ONE query
a row, the head for each of the step's rows), a lower bound, over the peak,
divided by the device time of the mixed-step program (``trace_classes.json``
``modules.mixed_step``: its median duration in the trace). The operations are
the median over the ``mixed`` records dispatched inside the capture, as the
time is a median. A step also reads 7.2 GB of int8 layers whatever it carries
(8.7 ms at the peak bandwidth) and moves ~15 rows' states: at 544 tokens a step
those bound it as much as the matrix unit does. Nothing for another
configuration, without a trace, or where a record lacks a counter (the parent
commit); over 100% is a fault of the count."""
from kda_bytes import is_kimi, mixed_step_flops
from stats import dispatched_in_capture, median

LAYER = "engine and model step"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def compute(sources):
    trace, config = sources.get("trace"), sources["config"]
    if not trace or not trace.get("mixed_step_s") or not is_kimi(config):
        return None
    records = dispatched_in_capture(sources)
    if records is None:
        return None
    counts = [mixed_step_flops(config, t) for t in records if t.get("kind") == "mixed"]
    if not counts or any(c is None for c in counts):
        return None
    return 100.0 * (median(counts) / sources["chip"]["bf16_flops_per_s"]) / trace["mixed_step_s"]
