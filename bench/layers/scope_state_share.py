"""Share of the device's busy time in the state layers' operators (``ssm.conv``,
``ssm.scan``, ``ssm.gate``, ``conv.short``, ``mamba``, ``gmu``): their projections and
gates beside the convolution and the scan. Self time by named scope, both step programs
together (``scope_times.class_share``: the class ``state`` of ``scope_classes.json``,
read off the capture's own HLO). Nothing where the class has no event, or where the
capture's HLO could not be had."""
from scope_times import class_share

LAYER = "engine and model step"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def compute(sources):
    return class_share(sources, "state")
