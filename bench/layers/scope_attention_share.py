"""Share of the device's busy time in the attention blocks (``attention``, ``attn.gate``,
``diff_attn``, ``dsa.index`` / ``dsa.select`` / ``dsa.attend``, ``mla.project`` /
``mla.absorb``, ``kv.write``): the block's norm and residual add, the projections, the
pools' writes, the kernels and the output projection. A state layer's operator inside
``attention`` goes to ``state`` by its own name. Self time by named scope, both step
programs together (``scope_times.class_share``: the class ``attention`` of
``scope_classes.json``, read off the capture's own HLO). Nothing where the class has no
event, or where the capture's HLO could not be had."""
from scope_times import class_share

LAYER = "engine and model step"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def compute(sources):
    return class_share(sources, "attention")
