"""Share of the device's busy time in operations of the two step programs under NO name of
the vocabulary: what the by-scope account cannot place (a loop's own instruction, a copy
the compiler put between two blocks). ``<trace>.by_scope.json`` names the five longest.
Self time by named scope, both step programs together (``scope_times.class_share``: the
class ``unscoped`` of ``scope_classes.json``, read off the capture's own HLO). Nothing
where the class has no event, or where the capture's HLO could not be had."""
from scope_times import class_share

LAYER = "engine and model step"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def compute(sources):
    return class_share(sources, "unscoped")
