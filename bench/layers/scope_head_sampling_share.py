"""Share of the device's busy time in the final norm, the rows' pick and the head
(``head``), the bias and a grammar's mask (``mask_bias``), and the draw with its noise
and log-probabilities (``sampling``). Self time by named scope, both step programs
together (``scope_times.class_share``: the class ``head_sampling`` of
``scope_classes.json``, read off the capture's own HLO). Nothing where the class has no
event, or where the capture's HLO could not be had."""
from scope_times import class_share

LAYER = "engine and model step"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def compute(sources):
    return class_share(sources, "head_sampling")
