"""Share of the device's busy time in the expert layers (``moe.router``, ``moe.dispatch``,
``moe.experts``, ``moe.combine``, ``moe.shared``): everything from the router to the
combined rows, not the two kernels alone. Self time by named scope, both step programs
together (``scope_times.class_share``: the class ``moe`` of ``scope_classes.json``, read
off the capture's own HLO). Nothing where the class has no event, or where the capture's
HLO could not be had."""
from scope_times import class_share

LAYER = "engine and model step"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def compute(sources):
    return class_share(sources, "moe")
