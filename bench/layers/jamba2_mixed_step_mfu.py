"""Share of the chip's bf16 peak that a mixed step of a ``jamba`` configuration's
matrix products reach: the operations of the tokens a step carried
(``mamba1_bytes.mixed_step_flops``: every packed token's four projections in
each of the 26 mixers, the attention layers' four, the dense MLP in each layer,
attention over each row's context counted as ONE query a row, the head for each
of the step's rows), a lower bound, over the peak, divided by the device time of
the mixed-step program (``trace_classes.json`` ``modules.mixed_step``: its median
duration in the trace). The operations are the median over the ``mixed`` records
dispatched inside the capture, as the time is a median. The share of the WHOLE
step: the selective scan, the convolution, the norms and the gate are vector
work that adds to the time and nothing to the count, and a step also reads 2.9
GB of int8 layers whatever it carries (3.5 ms at the peak bandwidth). Nothing
for another configuration, without a trace, or where a record lacks a counter
(the parent commit); over 100% is a fault of the count."""
from mamba1_bytes import is_jamba, mixed_step_flops
from stats import dispatched_in_capture, median

LAYER = "engine and model step"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def compute(sources):
    trace, config = sources.get("trace"), sources["config"]
    if not trace or not trace.get("mixed_step_s") or not is_jamba(config):
        return None
    records = dispatched_in_capture(sources)
    if records is None:
        return None
    counts = [mixed_step_flops(config, t) for t in records if t.get("kind") == "mixed"]
    if not counts or any(c is None for c in counts):
        return None
    return 100.0 * (median(counts) / sources["chip"]["bf16_flops_per_s"]) / trace["mixed_step_s"]
