"""How close a decode ROUND of a ``pangu_ultra_moe`` configuration comes to the
memory roofline: the bytes a round must read, a lower bound counted from the
file's sizes and the chunk's own record (``mtp_bytes.round_bytes``: every held
matrix once, the experts by the record's ``experts_read``, each row's latent
context ONCE for its two queries, the head), over the chip's peak bandwidth,
divided by the decode chunk program's device time a round
(``trace_classes.json`` ``modules.decode_chunk``: its median duration in the
trace, over ``decode_chunk`` rounds; a chunk that left early made fewer, which a
closed loop with every slot busy does not). The bytes are the median over the
``decode`` records dispatched inside the capture, as the time is a median.
Nothing for another configuration, without a trace, or where a record lacks a
counter (the parent commit); over 100% is a fault of the count."""
from mtp_bytes import round_bytes, round_share

LAYER = "kernels"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def compute(sources):
    return round_share(sources, round_bytes, "hbm_bytes_per_s")
