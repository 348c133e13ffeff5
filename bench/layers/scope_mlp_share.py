"""Share of the device's busy time in the dense feed-forward blocks (``mlp``): norm, the
matrices, the residual add. An expert layer's parts inside ``mlp`` go to ``moe`` by
their own names. Self time by named scope, both step programs together
(``scope_times.class_share``: the class ``mlp`` of ``scope_classes.json``, read off the
capture's own HLO). Nothing where the class has no event, or where the capture's HLO
could not be had."""
from scope_times import class_share

LAYER = "engine and model step"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def compute(sources):
    return class_share(sources, "mlp")
