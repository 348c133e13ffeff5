"""Share of the chip's bf16 peak that a decode ROUND of a ``pangu_ultra_moe``
configuration's matrix products reach: the operations of the round's query
tokens (``mtp_bytes.round_flops``: two tokens a live row through the layers'
projections, the dense MLP, the shared expert and the held experts' expected
rows; the module's tokens through its block and W_eh; the latent read as two
queries a row over the row's context; the head for each query), a lower bound,
over the peak, divided by the decode chunk program's device time a round
(``trace_classes.json`` ``modules.decode_chunk``: its median duration over
``decode_chunk`` rounds). The whole step's share: the round is the program that
emits this cell's tokens. The operations are the median over the ``decode``
records dispatched inside the capture. A round also reads ~8 GB of int8
whatever it carries (9.7 ms at the peak bandwidth): at 128 query tokens that
bounds it more than the matrix unit does. Nothing for another configuration,
without a trace, or where a record lacks a counter; over 100% is a fault of the
count."""
from mtp_bytes import round_flops, round_share

LAYER = "engine and model step"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def compute(sources):
    return round_share(sources, round_flops, "bf16_flops_per_s")
