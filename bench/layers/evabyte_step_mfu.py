"""Share of the chip's bf16 peak that a MIXED step of an ``evabyte`` configuration's
matrix products reach: the operations of the step's packed tokens
(``eva_bytes.mixed_step_flops``: every token through the layers' seven matrices, the
head a row, and the attention's scores and weighted values over the (query, cached
row) pairs the record counts, ``eva_pairs``), a lower bound, over the peak, divided by
the mixed step program's device time (``trace_classes.json`` ``modules.mixed_step``:
its median duration in the trace). The whole step's share: prompt chunks are where
this cell's operations are (a 512-token chunk is 6.6 TFLOP; a decode step of 5 rows
65 GFLOP). The operations are the median over the ``mixed`` records dispatched inside
the capture. Nothing for another configuration, without a trace, or where a record
lacks a counter; over 100% is a fault of the count."""
from eva_bytes import mixed_step_flops, step_share

LAYER = "engine and model step"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def compute(sources):
    return step_share(sources, "mixed", mixed_step_flops, (sources.get("trace") or {}).get("mixed_step_s"),
                      "bf16_flops_per_s")
